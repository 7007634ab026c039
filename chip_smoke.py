#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process (a chip belongs to one process
at a time), through the entry points a user calls, at the flagship width
— RetinaNet ResNet-50-FPN, frozen BN, bf16, the 800x1344 bucket, batch 8
per chip, weights random from a seed, depth cut to a few steps:

    kernels   every Pallas kernel that is selectable, compiled by Mosaic
              at the flagship shape and compared with its jnp path
    train     ``train.main`` → N steps, one eval through run_coco_eval,
              one checkpoint
    step      the train step as lowered on this backend contains the
              fused Pallas matching call
    export    ``convert_model.main`` from that checkpoint
    serve     ``serve.frontend.main`` offline over a few of the JPEGs
    multichip (``--chips 4`` only) the train phase again, data-parallel
              over four chips, then: replicas on four devices and
              bit-identical, batch shards on four devices, memory in use
              on all four

``main()`` demands a TPU and nothing makes it run on the CPU.  The phases
are plain functions of a :class:`SmokeSize`, so the CPU tests
(tests/unit/test_chip_smoke.py) call them at ``resnet_test``/64x64 with
the kernels in interpret mode.

Per phase it prints wall seconds and how many programs were compiled
against loaded from the persistent compile cache.  Those are set-up
facts of this run, not rates, and are written nowhere under a metric's
name.  Any phase that raises ends the run non-zero.  On success the last
line of stdout is one JSON object: ``{"ok": true, "device": {...}}``.

Everything is written under ``chiprun_out/chip_smoke/`` (the directory
the chip tool copies back); the bulky parts (images, checkpoint, export)
are removed before exit so what comes back is logs and summaries.  The
only other thing written into the checkout is the compile cache
(``.jax_cache/``, or wherever ``JAX_COMPILATION_CACHE_DIR`` points).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO_ROOT, "chiprun_out", "chip_smoke")
# Removed before exit: a flagship checkpoint + export is ~0.5 GB, past
# what the chip tool copies back.
_BULKY = ("data", "snapshot", "export")

# The tiles the kernels phase checks each Pallas kernel at, beside its
# module's default (VMEM bounds them; the focal backward holds more live
# temporaries than the forward, hence its lower ceiling).
MATCHING_TILES = (4096, 8192, 16384)
NMS_BLOCKS = (128, 256, 512)
FOCAL_FWD_TILES = (4096, 8192, 16384)
FOCAL_BWD_TILES = (2048, 4096)


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """One model at one size.  ``FLAGSHIP`` is what ``main()`` runs."""

    backbone: str
    norm: str
    f32: bool
    min_side: int
    max_side: int
    bucket: tuple[int, int]  # synthetic images are generated at exactly this
    per_chip_batch: int
    max_gt: int
    steps: int
    platform: str
    classes: int = 3
    serve_images: int = 5
    # An untrained head scores every anchor near its 0.01 prior, under the
    # 0.05 a deployment uses; at that threshold eval and serve would see no
    # detection at all and prove nothing about decode, NMS or conversion.
    score_threshold: float = 0.001

    @property
    def interpret(self) -> bool:
        """Pallas kernels in interpreter mode — the only way to run them
        off the TPU, i.e. in the CPU tests."""
        return self.platform != "tpu"


FLAGSHIP = SmokeSize(
    backbone="resnet50",
    norm="frozen_bn",
    f32=False,
    min_side=800,
    max_side=1344,
    bucket=(800, 1344),
    per_chip_batch=8,
    max_gt=100,
    steps=8,
    platform="tpu",
)


class SmokeFailure(AssertionError):
    """A phase ran and its output is wrong."""


def _check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# Per-phase set-up facts: wall time and compile-cache traffic
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def phase(name: str, record: list):
    """Time one phase and attribute the compiles inside it, from the
    program's own set-up record (``utils/backend.py::compile_stats``: JAX's
    compile events as phases of ``obs/trace.py``).  ``compile_requests`` go
    through the persistent cache; ``loaded_from_cache`` were found in it;
    ``compiled`` were not, and ``written_to_cache`` of them were stored for
    the next run.  An exception propagates — a failed phase fails the run."""
    from batchai_retinanet_horovod_coco_tpu.utils.backend import compile_stats

    print(f"--- phase {name} ---", flush=True)
    before = compile_stats()
    t0 = time.monotonic()
    yield
    wall = time.monotonic() - t0
    after = compile_stats()
    facts = {
        "phase": name,
        "wall_s": round(wall, 1),
        "compile_requests": after["requests"] - before["requests"],
        "loaded_from_cache": after["hits"] - before["hits"],
        "compiled": after["misses"] - before["misses"],
        "written_to_cache": after["written"] - before["written"],
        "backend_compile_s": round(after["compile_s"] - before["compile_s"], 1),
    }
    record.append(facts)
    print(
        f"phase {name}: ok, {facts['wall_s']} s wall; programs compiled "
        f"{facts['compiled']} ({facts['backend_compile_s']} s in the "
        f"compiler, {facts['written_to_cache']} written to the cache), "
        f"loaded from the cache {facts['loaded_from_cache']}",
        flush=True,
    )


# ---------------------------------------------------------------------------
# Phase: kernels
# ---------------------------------------------------------------------------


def _scene(rng, batch: int, num_gt: int, hw: tuple[int, int]):
    """Padded gt boxes the way the pipeline ships them: a random number
    of real rows per image, the rest masked padding."""
    import numpy as np

    h, w = hw
    boxes = np.zeros((batch, num_gt, 4), np.float32)
    mask = np.zeros((batch, num_gt), bool)
    for b in range(batch):
        n = int(rng.integers(1, num_gt + 1))
        xy = rng.uniform(0, [w * 0.9, h * 0.9], (n, 2))
        wh = rng.uniform(min(h, w) / 50, min(h, w) / 3, (n, 2))
        boxes[b, :n, :2] = xy
        boxes[b, :n, 2] = np.minimum(xy[:, 0] + wh[:, 0], w)
        boxes[b, :n, 3] = np.minimum(xy[:, 1] + wh[:, 1], h)
        mask[b, :n] = True
    labels = rng.integers(0, 80, (batch, num_gt)).astype(np.int32)
    return boxes, labels, mask


def _kernel_matching(size: SmokeSize, anchors, rng) -> list[str]:
    import jax
    import numpy as np

    from batchai_retinanet_horovod_coco_tpu.ops import matching as M
    from batchai_retinanet_horovod_coco_tpu.ops.pallas.matching import TILE_A

    done = []
    jnp_path = M.MatchingConfig(fused_pallas=False)
    # G = the explicit --max-gt of the flagship recipe, and the 8 that
    # resolve_max_gt auto-sizes synthetic data to (a contraction length
    # far from a multiple of 128).
    for num_gt in sorted({size.max_gt, 8}):
        boxes, labels, mask = _scene(
            rng, size.per_chip_batch, num_gt, size.bucket
        )

        def assign(config):
            return jax.jit(
                lambda b, l, m: M.anchor_targets_compact_batched(
                    anchors, b, l, m, config, planar_box_targets=True
                )
            )(boxes, labels, mask)

        want = jax.device_get(assign(jnp_path))
        for tile in sorted({TILE_A, *MATCHING_TILES}):
            got = jax.device_get(
                assign(
                    M.MatchingConfig(
                        fused_pallas=True,
                        pallas_interpret=size.interpret,
                        pallas_tile_a=tile,
                    )
                )
            )
            tag = f"matching G={num_gt} tile_a={tile}"
            bad = int(np.sum(got.state != want.state))
            _check(bad == 0, f"{tag}: {bad} anchor states differ")
            pos = want.state == M.POSITIVE
            _check(bool(pos.any()), f"{tag}: scene has no positive anchor")
            bad = int(
                np.sum(got.matched_labels[pos] != want.matched_labels[pos])
            )
            _check(bad == 0, f"{tag}: {bad} positive labels differ")
            err = float(np.max(np.abs(got.box_targets - want.box_targets)))
            _check(err <= 1e-5, f"{tag}: box targets differ by {err}")
            done.append(tag)
    return done


def _kernel_nms(size: SmokeSize, anchors, rng) -> list[str]:
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        nms_fn_for,
    )
    from batchai_retinanet_horovod_coco_tpu.ops import boxes as boxes_lib
    from batchai_retinanet_horovod_coco_tpu.ops.pallas.nms import (
        DEFAULT_BLOCK_K,
    )

    # Decoded anchors with small deltas: neighbours overlap heavily, so
    # the suppression chains are long; sigmoid(-4 ± 1) gives far more
    # than pre_nms_size candidates above the score threshold.
    batch, num_anchors = size.per_chip_batch, anchors.shape[0]
    logits = jnp.asarray(
        rng.normal(-4.0, 1.0, (batch, num_anchors, 80)).astype(np.float32)
    )
    deltas = jnp.asarray(
        rng.normal(0.0, 0.3, (batch, num_anchors, 4)).astype(np.float32)
    )
    base = DetectConfig(pre_nms_size=1000, nms_block_k=DEFAULT_BLOCK_K)

    def post(config):
        nms = nms_fn_for(config)

        def run(cls_logits, box_deltas):
            boxes = boxes_lib.decode_boxes(
                anchors[None], box_deltas, config.codec
            )
            boxes = boxes_lib.clip_boxes(boxes, size.bucket)
            return nms(boxes, jax.nn.sigmoid(cls_logits))

        return jax.device_get(jax.jit(run)(logits, deltas))

    want = post(dc.replace(base, nms_impl="xla"))
    _check(
        int(want.valid.sum()) > batch,
        "nms: the reference kept almost nothing; the scene is degenerate",
    )
    done = []
    for block_k in sorted({DEFAULT_BLOCK_K, *NMS_BLOCKS}):
        got = post(
            dc.replace(
                base,
                nms_impl="pallas",
                nms_block_k=block_k,
                nms_interpret=size.interpret,
            )
        )
        tag = f"nms block_k={block_k}"
        for field in ("valid", "labels", "scores", "boxes"):
            same = np.array_equal(getattr(got, field), getattr(want, field))
            _check(same, f"{tag}: detections differ in {field!r}")
        done.append(tag)
    return done


def _kernel_focal(size: SmokeSize, num_anchors: int, rng) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from batchai_retinanet_horovod_coco_tpu import losses as L
    from batchai_retinanet_horovod_coco_tpu.ops.pallas import focal

    batch = size.per_chip_batch
    logits = jnp.asarray(
        rng.normal(-4.0, 1.0, (batch, num_anchors, 80)).astype(np.float32)
    )
    labels = jnp.asarray(
        rng.integers(0, 80, (batch, num_anchors)).astype(np.int32)
    )
    state = jnp.asarray(
        rng.choice(
            np.array([-1, 0, 1], np.int32),
            (batch, num_anchors),
            p=[0.04, 0.95, 0.01],
        )
    )

    def value_and_grad(config):
        return jax.device_get(
            jax.jit(
                jax.value_and_grad(
                    lambda x: L.focal_loss_compact(x, labels, state, config)
                )
            )(logits)
        )

    want, want_grad = value_and_grad(L.LossConfig(pallas_focal=False))
    # The fwd and bwd lists are independent: pair them off, cycling the
    # shorter one.
    fwd = sorted({focal.FWD_TILE_A, *FOCAL_FWD_TILES})
    bwd = sorted({focal.BWD_TILE_A, *FOCAL_BWD_TILES})
    done = []
    for i in range(max(len(fwd), len(bwd))):
        fwd_tile, bwd_tile = fwd[i % len(fwd)], bwd[i % len(bwd)]
        got, got_grad = value_and_grad(
            L.LossConfig(
                pallas_focal=True,
                pallas_interpret=size.interpret,
                focal_fwd_tile_a=fwd_tile,
                focal_bwd_tile_a=bwd_tile,
            )
        )
        tag = f"focal fwd_tile={fwd_tile} bwd_tile={bwd_tile}"
        _check(
            bool(np.isfinite(got)) and abs(got - want) <= 1e-4 * abs(want),
            f"{tag}: loss {got} against jnp {want}",
        )
        # Transcendentals differ in the last bits between the two
        # lowerings; 1e-3 of the largest gradient is far above that and
        # far below any real disagreement.
        err = float(np.max(np.abs(got_grad - want_grad)))
        scale = float(np.max(np.abs(want_grad)))
        _check(err <= 1e-3 * scale, f"{tag}: grad differs by {err} of {scale}")
        done.append(tag)
    return done


def phase_kernels(size: SmokeSize) -> list[str]:
    """Compile every selectable Pallas kernel at ``size`` and compare it
    with its jnp path; returns the names of the cases that agreed."""
    import jax.numpy as jnp
    import numpy as np

    from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib

    anchors = jnp.asarray(anchors_lib.anchors_for_image_shape(size.bucket))
    rng = np.random.default_rng(0)
    done = _kernel_matching(size, anchors, rng)
    done += _kernel_nms(size, anchors, rng)
    done += _kernel_focal(size, anchors.shape[0], rng)
    for tag in done:
        print(f"  agreed: {tag}", flush=True)
    return done


# ---------------------------------------------------------------------------
# Phase: train (+ eval + checkpoint), through train.main
# ---------------------------------------------------------------------------


def _model_flags(size: SmokeSize) -> list[str]:
    flags = ["--backbone", size.backbone, "--norm", size.norm]
    return flags + (["--f32"] if size.f32 else [])


def _read_jsonl(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def phase_train(size: SmokeSize, work: str, num_devices: int = 1) -> dict:
    """``train.main`` for ``size.steps`` steps on ``num_devices`` chips:
    every step logged with a finite loss, one eval, one checkpoint."""
    import train

    tag = "train" if num_devices == 1 else f"train{num_devices}"
    global_batch = size.per_chip_batch * num_devices
    log_dir = os.path.join(work, f"logs_{tag}")
    snapshot = os.path.join(work, "snapshot", tag)
    h, w = size.bucket
    train.main([
        "synthetic",
        "--synthetic-root", os.path.join(work, "data", tag),
        "--synthetic-images", str(max(16, 2 * global_batch)),
        "--synthetic-classes", str(size.classes),
        "--synthetic-size", f"{h}x{w}",
        *_model_flags(size),
        "--image-min-side", str(size.min_side),
        "--image-max-side", str(size.max_side),
        "--batch-size", str(global_batch),
        "--max-gt", str(size.max_gt),
        "--steps", str(size.steps),
        "--warmup-steps", "2",
        "--eval-every", str(size.steps),
        "--log-every", "1",
        "--snapshot-path", snapshot,
        "--checkpoint-every", str(size.steps),
        "--no-resume",
        "--log-dir", log_dir,
        "--score-threshold", str(size.score_threshold),
        "--platform", size.platform,
        "--num-devices", str(num_devices),
    ])

    result = check_train_log(size, tag, log_dir, snapshot)
    print(
        f"  {tag}: losses "
        f"{[round(v, 4) for _, v in sorted(result['losses'].items())]}\n"
        f"  {tag}: eval {result['eval']}",
        flush=True,
    )
    return result


def check_train_log(
    size: SmokeSize, tag: str, log_dir: str, snapshot: str
) -> dict:
    """What ``train.main`` left behind, not that it returned: every step
    logged with a finite loss, one eval with finite metrics, the newest
    checkpoint at the last step."""
    import math

    from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import latest_step

    records = _read_jsonl(os.path.join(log_dir, "metrics.jsonl"))
    losses = {
        r["step"]: r["train/loss"] for r in records if "train/loss" in r
    }
    _check(
        sorted(losses) == list(range(1, size.steps + 1)),
        f"{tag}: steps logged {sorted(losses)}, wanted 1..{size.steps}",
    )
    bad = {s: v for s, v in losses.items() if not math.isfinite(v)}
    _check(not bad, f"{tag}: non-finite losses {bad}")
    evals = [r for r in records if any(k.startswith("eval/") for k in r)]
    _check(len(evals) == 1, f"{tag}: {len(evals)} eval records, wanted 1")
    eval_metrics = {
        k[len("eval/"):]: v for k, v in evals[0].items()
        if k.startswith("eval/")
    }
    _check("AP" in eval_metrics, f"{tag}: eval metrics lack AP: {eval_metrics}")
    _check(
        all(math.isfinite(v) for v in eval_metrics.values()),
        f"{tag}: non-finite eval metrics {eval_metrics}",
    )
    _check(
        latest_step(snapshot) == size.steps,
        f"{tag}: newest checkpoint is step {latest_step(snapshot)}",
    )
    return {"losses": losses, "eval": eval_metrics, "snapshot": snapshot}


def phase_step_program(size: SmokeSize) -> int:
    """The train step, lowered on this backend exactly as the loop builds
    it, contains the fused Pallas matching call.

    ``ops/matching.py`` picks the kernel from ``jax.default_backend()``
    at trace time; a step that silently took the XLA lowering would still
    train, so the choice is asserted, not assumed.  Returns the number of
    Mosaic custom calls found.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.train import (
        create_train_state,
        make_train_step,
    )

    model = build_retinanet(
        RetinaNetConfig(
            num_classes=size.classes,
            backbone=size.backbone,
            norm_kind=size.norm,
            dtype=jnp.float32 if size.f32 else jnp.bfloat16,
        )
    )
    h, w = size.bucket
    state = jax.eval_shape(
        lambda: create_train_state(
            model, optax.sgd(0.01, momentum=0.9), (1, h, w, 3),
            jax.random.key(0),
        )
    )
    b, g = size.per_chip_batch, size.max_gt
    batch = {
        "images": jax.ShapeDtypeStruct((b, h, w, 3), jnp.uint8),
        "gt_boxes": jax.ShapeDtypeStruct((b, g, 4), jnp.float32),
        "gt_labels": jax.ShapeDtypeStruct((b, g), jnp.int32),
        "gt_mask": jax.ShapeDtypeStruct((b, g), jnp.bool_),
    }
    text = make_train_step(model, size.bucket, size.classes).lower(
        state, batch
    ).as_text()
    calls = text.count("tpu_custom_call")
    _check(
        calls >= 1,
        "the lowered train step has no tpu_custom_call: it took the XLA "
        f"matching path (jax.default_backend() = {jax.default_backend()!r})",
    )
    print(f"  train step lowering: {calls} Mosaic custom call(s)", flush=True)
    return calls


# ---------------------------------------------------------------------------
# Phases: export and serve
# ---------------------------------------------------------------------------


def phase_export(size: SmokeSize, work: str, snapshot: str) -> str:
    """``convert_model.main`` from the train phase's checkpoint: one
    bucket, one batch size."""
    import convert_model

    export_dir = os.path.join(work, "export")
    h, w = size.bucket
    manifest = convert_model.main([
        "--snapshot-path", snapshot,
        "--output", export_dir,
        "--num-classes", str(size.classes),
        *_model_flags(size),
        "--buckets", f"{h}x{w}",
        "--batch-size", str(size.per_chip_batch),
        "--image-min-side", str(size.min_side),
        "--image-max-side", str(size.max_side),
        "--score-threshold", str(size.score_threshold),
        "--platform", size.platform,
    ])
    with open(manifest) as f:
        artifacts = json.load(f)["artifacts"]
    _check(len(artifacts) == 1, f"export wrote {len(artifacts)} artifacts")
    return export_dir


def phase_serve(size: SmokeSize, work: str, export_dir: str) -> dict:
    """``serve.frontend.main`` in offline mode over a few of the synthetic
    JPEGs: one detections line per image, no request failed, and every
    image has detections that are finite, inside it and scored in range."""
    import math

    from batchai_retinanet_horovod_coco_tpu.serve import frontend

    src = os.path.join(work, "data", "train", "val")
    requests = os.path.join(work, "data", "serve_requests")
    os.makedirs(requests)
    names = sorted(os.listdir(src))[: size.serve_images]
    for name in names:
        shutil.copy(os.path.join(src, name), requests)
    output = os.path.join(work, "detections.jsonl")
    stats = frontend.main([
        "--export-dir", export_dir,
        "--images", requests,
        "--output", output,
        "--platform", size.platform,
    ])
    records = _read_jsonl(output)
    _check(
        [r["file"] for r in records] == names,
        f"served {[r['file'] for r in records]}, sent {names}",
    )
    failed = [r for r in records if "detections" not in r]
    _check(not failed, f"requests failed: {failed}")
    h, w = size.bucket  # the JPEGs were generated at exactly this size
    for r in records:
        _check(bool(r["detections"]), f"{r['file']}: no detection")
        for d in r["detections"]:
            x, y, bw, bh = d["bbox"]
            ok = (
                all(math.isfinite(v) for v in (x, y, bw, bh, d["score"]))
                and -1 <= x <= x + bw <= w + 1
                and -1 <= y <= y + bh <= h + 1
                and size.score_threshold <= d["score"] <= 1
                and 0 <= d["category_id"] < size.classes
            )
            _check(ok, f"{r['file']}: detection out of range: {d}")
    print(
        f"  served {len(records)} images, "
        f"{sum(len(r['detections']) for r in records)} detections",
        flush=True,
    )
    return stats


# ---------------------------------------------------------------------------
# Phase: multichip
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _watch_placement(seen: dict):
    """Let ``train.main`` run untouched, but note where the loop put
    things: the devices of each batch's shards, and the final state.
    (``train.main`` returns only the step count; what this phase asserts
    is about live arrays.)"""
    from batchai_retinanet_horovod_coco_tpu.train import loop

    device_batch, run_training = loop._device_batch, loop.run_training

    def noting_device_batch(batch, mesh, task):
        arrays = device_batch(batch, mesh, task)
        seen["batch_devices"] = sorted(
            s.device.id for s in arrays["images"].addressable_shards
        )
        return arrays

    def noting_run_training(*args, **kwargs):
        seen["state"] = run_training(*args, **kwargs)
        return seen["state"]

    loop._device_batch, loop.run_training = (
        noting_device_batch, noting_run_training,
    )
    try:
        yield
    finally:
        loop._device_batch, loop.run_training = device_batch, run_training


def check_placement(seen: dict, num_devices: int) -> None:
    """What "everything on the first chip" would break."""
    import jax
    import numpy as np

    _check(
        len(set(seen["batch_devices"])) == num_devices,
        f"batch shards sit on devices {seen['batch_devices']}, "
        f"wanted {num_devices} distinct",
    )
    leaves = jax.tree.leaves(seen["state"].params)
    _check(bool(leaves), "the final state has no parameters")
    for leaf in leaves:
        _check(
            len(leaf.sharding.device_set) == num_devices,
            f"a {leaf.shape} parameter lives on "
            f"{len(leaf.sharding.device_set)} device(s)",
        )
        replicas = [np.asarray(s.data) for s in leaf.addressable_shards]
        _check(
            all(r.tobytes() == replicas[0].tobytes() for r in replicas[1:]),
            f"replicas of a {leaf.shape} parameter are not bit-identical",
        )
    print(
        f"  {len(leaves)} parameter leaves replicated bit-identically over "
        f"{num_devices} devices; batch shards on {seen['batch_devices']}",
        flush=True,
    )


def check_memory_in_use(num_devices: int) -> None:
    import jax

    in_use = {
        d.id: d.memory_stats()["bytes_in_use"]
        for d in jax.devices()[:num_devices]
    }
    # The replicated parameters alone are tens of MB per chip.
    _check(
        all(v > 16 * 2**20 for v in in_use.values()),
        f"bytes_in_use per device {in_use}: some chip holds nothing",
    )
    print(f"  bytes_in_use per device: {in_use}", flush=True)


def phase_multichip(size: SmokeSize, work: str, num_devices: int) -> dict:
    """The train phase again, data-parallel over ``num_devices`` chips,
    then the placement checks while the final state is still alive."""
    seen: dict = {}
    with _watch_placement(seen):
        result = phase_train(size, work, num_devices=num_devices)
    check_placement(seen, num_devices)
    if size.platform == "tpu":  # CPU devices report no memory statistics
        check_memory_in_use(num_devices)
    return result


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def _versions() -> dict:
    from importlib import metadata

    return {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): needs one chip and uses one.  4: needs four, "
             "and adds the data-parallel phase",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform="
            f"{device['platform']!r} ({device['kind']!r} x{device['count']})"
            ".  No phase was run.",
            file=sys.stderr,
        )
        return 2
    if device["count"] < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
            f"JAX found {device['count']}.  No phase was run.",
            file=sys.stderr,
        )
        return 2

    from batchai_retinanet_horovod_coco_tpu.utils.backend import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    versions = _versions()
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']!r} devices={device['count']} "
        f"(using {args.chips})\n"
        f"chip_smoke: {' '.join(f'{k}={v}' for k, v in versions.items())}\n"
        f"chip_smoke: compile cache at {cache_dir}",
        flush=True,
    )

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    phases: list[dict] = []
    size = FLAGSHIP
    t0 = time.monotonic()
    try:
        with phase("kernels", phases):
            kernels = phase_kernels(size)
        with phase("train", phases):
            trained = phase_train(size, OUT_DIR)
            from batchai_retinanet_horovod_coco_tpu.native.build import (
                library_origin,
            )

            print(
                "  COCO matching ran in "
                + {
                    "built": "the kernel built from native/cocoeval.cpp "
                             "in this run",
                    "reused": "libcocoeval.so, built from native/"
                              "cocoeval.cpp by an earlier run in this "
                              "checkout",
                    None: "numpy (the native kernel was not built)",
                }[library_origin()],
                flush=True,
            )
        with phase("step", phases):
            phase_step_program(size)
        with phase("export", phases):
            export_dir = phase_export(size, OUT_DIR, trained["snapshot"])
        with phase("serve", phases):
            phase_serve(size, OUT_DIR, export_dir)
        if args.chips > 1:
            with phase("multichip", phases):
                phase_multichip(size, OUT_DIR, args.chips)
    finally:
        for name in _BULKY:
            shutil.rmtree(os.path.join(OUT_DIR, name), ignore_errors=True)

    summary = {
        "ok": True,
        "device": device,
        "chips_used": args.chips,
        "versions": versions,
        "compile_cache": cache_dir,
        "total_wall_s": round(time.monotonic() - t0, 1),
        "phases": phases,
        "kernels_agreed": kernels,
    }
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    print(f"chip_smoke: all phases passed in {summary['total_wall_s']} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
