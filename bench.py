"""Benchmark: flagship train-step AND eval/detect throughput, ONE JSON line.

``--mode train`` (default) measures images/sec/chip for the full jitted
SPMD training step (forward, on-device target assignment, focal +
smooth-L1 losses, backward, optimizer update) on RetinaNet ResNet-50-FPN
at the reference's flagship resolution bucket (800x1344, BASELINE.json:10),
bf16 compute.

``--mode eval`` measures the eval fast path (ISSUE 2, BASELINE.json
configs[4] "on-device batched NMS"): per live bucket, the AOT-compiled
detect program (forward → sigmoid → decode → clip → batched NMS) in
ms/batch and imgs/s/chip, the POST-PROCESS alone (sigmoid+decode+clip+NMS
on synthetic head outputs — the tripwire for the 30-40x NMS/top-k rewrite
history, ops/nms.py), and an end-to-end sequential-vs-pipelined
``run_coco_eval`` comparison (the measured speedup of the overlapped
driver, plus a bit-identity check of its detections).

Every mode runs in this one process on whatever device JAX finds, names
that device in its JSON line (``device_kind``), and fails when anything
fails: an out-of-memory at the requested batch is an error, not a reason
to measure a smaller batch, and there is no stored number to print in
place of a measurement.

``--mode serve`` measures the dynamic-batching inference server (ISSUE 4,
serve/): per live bucket it AOT-builds the same detect executable the
server dispatches, measures the in-run sequential detect CEILING on it,
then drives the server with a saturating closed loop (2×batch client
threads, steady-state window after a warm period) and reports imgs/s,
``vs_ceiling`` (the acceptance bar: ≥0.9 on the chip), p50/p99 request
latency, and an overload leg — an open-loop flood against tiny bounded
queues that must SHED (reject-with-reason, every accepted request
resolves, bounded p99) rather than queue unboundedly.
Knobs: SERVEBENCH_STEPS (window), SERVEBENCH_OVERLOAD=0 (skip the
overload leg), BENCH_SWEEP=0 (flagship bucket only).

``--mode comm`` measures the gradient-communication subsystem (ISSUE 13,
comm/) on a forced COMMBENCH_DEVICES-wide virtual CPU mesh: static
bytes-on-wire vs the exact schedule (the ROADMAP's ≤ 0.65× claim, from
the plan arithmetic — device-independent), step-time delta per variant
(int8 / int8+overlap / bf16 / 1 MB buckets; indicative only on the
virtual mesh), and loss/param parity drift after N identical steps vs
the exact run.  The hierarchical leg (ISSUE 16) routes the int8 policy
through the two-fabric tree on an emulated 2-slice topology and records
the per-hop split: DCN bytes ≤ 0.65× the all-exact hierarchical tree,
ZERO quantized ICI bytes, drift in the flat band.  The committed record
is COMMBENCH.json (written by ``scripts/commbench_sweep.py`` /
COMMBENCH_OUT); ``make commbench-check`` is the tripwire (bytes ratio
hard ≤ 0.65 AND ≤ committed + 0.02, the per-hop claims, parity-drift
band, device-class guard).

Bucket sweep (round 4, VERDICT r3 missing #3): the multiscale pipeline
emits TWO static buckets at the flagship 800/1333 config
(data/pipeline.default_buckets: 800x1344 landscape+near-square, 1344x800
portrait; the former third 1088x1088 bucket was proven unreachable and
dropped in round 5 — see default_buckets' docstring) — the training
wall-clock model must not assume every step runs at the landscape-bucket
rate.  By default the bench sweeps both and reports ``per_bucket``
imgs/s/chip plus ``weighted_mix``, the COCO-aspect-share-weighted rate
(shares below).  ``value`` stays the flagship 800x1344 number so
round-over-round comparisons hold.  BENCH_SWEEP=0 restores the
single-bucket bench.

In sweep mode the flagship-only line prints FIRST and the full line
(same schema + sweep keys) LAST: any consumer that reads either the
first or the last JSON line gets a valid record, even if the process is
killed mid-sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from batchai_retinanet_horovod_coco_tpu.obs import trace as obs_trace

BUCKET = (800, 1344)


def _artifact_path(name: str) -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), name)


WARMUP_STEPS = 5
# 60 steps ≈ 7.5 s of device time, split into two windows whose spread
# is reported beside the value as the run's own noise floor.
MEASURE_STEPS = 60

# Approximate share of COCO train2017 images landing in each bucket the
# flagship-config pipeline emits, keyed by the bucket's ASPECT CLASS so
# a reorder of default_buckets cannot silently swap shares: landscape
# AND square images land in 800x1344 (every resized landscape/square
# fits it), portraits (any severity) in 1344x800 — the exhaustive
# routing scan in tests/unit/test_buckets.py pins this keying against
# data/pipeline.bucket_for_source.  Shares are ESTIMATES from the
# public COCO size distribution (~640x480-class landscape dominates;
# portraits ~25%); re-derive exactly with `debug.py buckets` on the
# real annotations.
_MIX_SHARES = {"landscape": 0.77, "portrait": 0.23}


def sweep_buckets() -> tuple[tuple[tuple[int, int], float], ...]:
    """(bucket, share) pairs — shapes from the pipeline's single source
    of truth (default_buckets), so the sweep cannot silently drift from
    the shapes a training run actually compiles; only the COCO share
    estimates live here, keyed by aspect class."""
    from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
        default_buckets,
    )

    buckets = default_buckets(800, 1333)
    # Runtime schema checks, not debug asserts: under `python -O` a bare
    # assert would vanish and a reordered default_buckets could silently
    # pair shares with the wrong shapes.
    if buckets[0] != BUCKET:
        raise RuntimeError(
            f"default_buckets(800, 1333) now leads with {buckets[0]}, not "
            f"{BUCKET} — update BUCKET (the round-over-round headline "
            "shape) and _MIX_SHARES together"
        )
    if len(buckets) == 1:
        return ((buckets[0], 1.0),)

    def aspect_class(hw: tuple[int, int]) -> str:
        h, w = hw
        return "landscape" if h < w else ("portrait" if h > w else "square")

    classes = [aspect_class(b) for b in buckets]
    if sorted(classes) != sorted(_MIX_SHARES):
        raise RuntimeError(
            f"default_buckets aspect classes {classes} no longer match the "
            f"share table {sorted(_MIX_SHARES)} — update _MIX_SHARES"
        )
    return tuple((b, _MIX_SHARES[c]) for b, c in zip(buckets, classes))


# Fewer timed steps for the non-flagship buckets: they only feed the
# weighted mix, and the sweep must stay under the driver's bench budget.
SWEEP_MEASURE_STEPS = 30

def _device_peak_tflops() -> float | None:
    """Spec-sheet peak for this device, None for a device the table does
    not know.  The table lives in obs/analyze — ONE source of truth with
    the per-run report."""
    from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
        device_peak_tflops,
    )

    return device_peak_tflops(jax.devices()[0].device_kind)[0]


def _trace_attribution() -> dict | None:
    """The analyzer's span attribution over this process's live rings
    (--trace runs only): folded into the JSON line so a record carries
    data_wait%/overlap% alongside imgs/s and schedule provenance."""
    if not obs_trace.enabled():
        return None
    try:
        from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
            span_attribution,
        )

        return span_attribution(obs_trace.snapshot_events())
    except Exception as e:  # attribution must never fail the bench
        print(f"# trace attribution failed: {e!r}", flush=True)
        return None


def make_batch(batch_size: int, hw: tuple[int, int], max_gt: int = 100):
    rng = np.random.default_rng(0)
    h, w = hw
    gt_boxes = np.zeros((batch_size, max_gt, 4), np.float32)
    gt_labels = np.zeros((batch_size, max_gt), np.int32)
    gt_mask = np.zeros((batch_size, max_gt), bool)
    for b in range(batch_size):
        n = int(rng.integers(4, 24))
        xy = rng.uniform(0, [w - 64, h - 64], (n, 2))
        wh = rng.uniform(16, 256, (n, 2))
        gt_boxes[b, :n, 0::2] = np.stack([xy[:, 0], np.minimum(xy[:, 0] + wh[:, 0], w)], 1)
        gt_boxes[b, :n, 1::2] = np.stack([xy[:, 1], np.minimum(xy[:, 1] + wh[:, 1], h)], 1)
        gt_labels[b, :n] = rng.integers(0, 80, n)
        gt_mask[b, :n] = True
    return {
        # uint8, as the pipeline ships it (normalization runs on device and
        # fuses into the stem; measured ~2% faster than feeding f32).
        "images": jnp.asarray(
            rng.integers(0, 256, (batch_size, h, w, 3), dtype=np.uint8)
        ),
        "gt_boxes": jnp.asarray(gt_boxes),
        "gt_labels": jnp.asarray(gt_labels),
        "gt_mask": jnp.asarray(gt_mask),
    }


def run_bench(
    batch_size: int,
    hw: tuple[int, int] = BUCKET,
    measure_steps: int = MEASURE_STEPS,
    numerics: bool = False,
) -> tuple[float, float | None]:
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.obs.numerics import (
        NumericsConfig,
    )
    from batchai_retinanet_horovod_coco_tpu.train import (
        create_train_state,
        make_train_step,
    )

    # frozen_bn is the reference's fine-tune configuration (BN frozen during
    # detection training, SURVEY.md M2) and measures ~9% faster than GN on
    # v5e (pure scale+bias fuses into the convs; GN's per-group moments are
    # extra bandwidth-bound passes).
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=80, backbone="resnet50", norm_kind="frozen_bn"
        )
    )
    state = create_train_state(
        model, optax.sgd(0.01, momentum=0.9), (1, *hw, 3), jax.random.key(0)
    )
    # numerics=True measures the ISSUE-10 in-step summary's overhead
    # (the JSON line's numerics_overhead field states the
    # on-vs-off delta); the default step is byte-identical to pre-ISSUE-10.
    step = make_train_step(
        model, hw, 80, donate_state=True,
        numerics=NumericsConfig(enabled=numerics),
    )
    batch = make_batch(batch_size, hw)

    # AOT-compile once: the executable both runs the loop and reports the
    # XLA-counted FLOPs of the whole step (forward, assignment, losses,
    # backward, update) for the MFU number.
    with obs_trace.span("aot_compile_train", bucket=f"{hw[0]}x{hw[1]}"):
        compiled = step.lower(state, batch).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else None
    step_flops = float(cost.get("flops", 0.0)) if cost else 0.0

    for _ in range(min(WARMUP_STEPS, measure_steps)):
        state, metrics = compiled(state, batch)
    # Sync before t0 so no warmup work leaks into the first window.
    float(metrics["loss"])

    # TWO disjoint timed windows: the point estimate alone cannot tell
    # noise from a real change; the window-to-window spread is a
    # same-run noise floor reported beside the value.  Each window syncs
    # INSIDE its timed region by pulling a scalar to the host — dispatch
    # is asynchronous, so a region that does not wait measures the
    # enqueue.
    half = max(1, measure_steps // 2)
    window_rates = []
    dt_total = 0.0
    for _ in range(2):
        with obs_trace.span("train_window", bucket=f"{hw[0]}x{hw[1]}"):
            t0 = time.perf_counter()
            for _ in range(half):
                state, metrics = compiled(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
        assert np.isfinite(loss)
        window_rates.append(batch_size * half / dt)
        dt_total += dt

    ips = batch_size * 2 * half / dt_total
    peak = _device_peak_tflops()
    mfu = None
    if step_flops > 0 and peak:
        achieved_tflops = step_flops * (2 * half / dt_total) / 1e12
        mfu = achieved_tflops / peak
    return ips, mfu, tuple(window_rates)


# --- eval mode (ISSUE 2: the detect/NMS fast path) -----------------------

EVAL_WARMUP_STEPS = 3


def _eval_model_and_state(num_classes: int = 80):
    """The flagship inference model (shared across buckets; fully conv, so
    the init shape is small and the params serve every bucket)."""
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state

    model = build_retinanet(
        RetinaNetConfig(
            num_classes=num_classes, backbone="resnet50",
            norm_kind="frozen_bn",
        )
    )
    state = create_train_state(
        model, optax.sgd(0.01, momentum=0.9), (1, 256, 256, 3),
        jax.random.key(0),
    )
    return model, state


def _sync_scalar(det) -> None:
    """Wait for the device by pulling a detection scalar to the host."""
    float(np.asarray(jax.device_get(det.scores))[0, 0])


def run_postprocess_bucket(
    batch_size: int, hw: tuple[int, int], measure_steps: int
) -> float:
    """ms/batch of the POST-PROCESS alone: sigmoid → decode → clip →
    batched NMS on synthetic head outputs at this bucket's anchor count.

    This is the isolation tripwire for ops/nms.py's fixed-point NMS and
    two-stage top-k (both carry measured 30-40x rewrite histories): a
    regression there moves this number even when the conv-bound full
    detect program hides it.
    """
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        nms_fn_for,
        resolve_detect_config,
    )
    from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
    from batchai_retinanet_horovod_coco_tpu.ops import boxes as boxes_lib

    # Schedule-resolved (tune/): measures the device's schedule winner
    # (impl + block + pre_nms_size), not a hardcoded config.
    cfg = resolve_detect_config(DetectConfig())
    anchors = anchors_lib.anchors_for_image_shape(hw, cfg.anchor)
    rng = np.random.default_rng(1)
    # sigmoid(-4 ± 1) ≈ 2% mean foreground probability: a realistic sparse
    # score field, so the score-threshold mask and top-k see typical work.
    cls = jnp.asarray(
        rng.normal(-4.0, 1.0, (batch_size, anchors.shape[0], 80)).astype(
            np.float32
        )
    )
    deltas = jnp.asarray(
        rng.normal(0.0, 0.3, (batch_size, anchors.shape[0], 4)).astype(
            np.float32
        )
    )
    anchors_dev = jnp.asarray(anchors)
    nms = nms_fn_for(cfg)

    def post(cls_logits, box_deltas):
        scores = jax.nn.sigmoid(cls_logits)
        boxes = boxes_lib.decode_boxes(anchors_dev[None], box_deltas, cfg.codec)
        boxes = boxes_lib.clip_boxes(boxes, hw)
        return nms(boxes, scores)

    compiled = jax.jit(post).lower(cls, deltas).compile()
    det = None
    for _ in range(2):
        det = compiled(cls, deltas)
    _sync_scalar(det)
    steps = max(1, measure_steps // 2)
    t0 = time.perf_counter()
    for _ in range(steps):
        det = compiled(cls, deltas)
    _sync_scalar(det)
    return round((time.perf_counter() - t0) / steps * 1e3, 2)


def run_eval_bucket(
    model, state, batch_size: int, hw: tuple[int, int], measure_steps: int
) -> dict:
    """One bucket's eval-path numbers: the AOT-compiled detect program
    (forward → decode → NMS) in two disjoint timed windows (same noise
    policy as the train bench) plus the postprocess-only figure."""
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        compile_detect_fn,
    )

    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.integers(0, 256, (batch_size, *hw, 3), dtype=np.uint8)
    )
    # AOT compile via the ONE shared bench/serve path (the span naming the
    # compile lives inside compile_detect_fn).
    call = compile_detect_fn(model, state, hw, batch_size, DetectConfig())
    det = None
    for _ in range(EVAL_WARMUP_STEPS):
        det = call(images)
    _sync_scalar(det)

    half = max(1, measure_steps // 2)
    window_rates = []
    dt_total = 0.0
    for _ in range(2):
        with obs_trace.span("eval_window", bucket=f"{hw[0]}x{hw[1]}"):
            t0 = time.perf_counter()
            for _ in range(half):
                det = call(images)
            _sync_scalar(det)
            dt = time.perf_counter() - t0
        window_rates.append(batch_size * half / dt)
        dt_total += dt
    ips = batch_size * 2 * half / dt_total
    return {
        "imgs_per_sec": round(ips, 3),
        "detect_ms_per_batch": round(dt_total / (2 * half) * 1e3, 2),
        "postprocess_ms_per_batch": run_postprocess_bucket(
            batch_size, hw, measure_steps
        ),
        "window_rates": [round(w, 3) for w in window_rates],
        "noise_pct": round(
            abs(window_rates[0] - window_rates[1]) / max(ips, 1e-9) * 100, 2
        ),
        "batch": batch_size,
    }


def run_e2e_compare() -> dict:
    """Measured end-to-end ``run_coco_eval`` wall-clock, sequential vs
    pipelined, on a synthetic COCO split — whether the three-stage
    overlap pays, plus an in-run bit-identity check of the two
    paths' detections.  Both passes share ONE compiled detect program
    (``detect_fns``), so the comparison times the drivers, not compiles.

    The head is sized to the synthetic palette (8 classes — every detect
    label must map through the dataset's ``label_to_cat_id``); the
    backbone/FPN cost, which dominates the device side, matches flagship.
    """
    import tempfile

    num_images = int(os.environ.get("EVALBENCH_E2E_IMAGES", "32"))
    size = int(os.environ.get("EVALBENCH_E2E_SIZE", "320"))
    batch = int(os.environ.get("EVALBENCH_E2E_BATCH", "4"))
    model, state = _eval_model_and_state(num_classes=8)
    tmp = tempfile.TemporaryDirectory(prefix="evalbench_")
    try:
        return _run_e2e_compare(tmp.name, model, state, num_images, size, batch)
    finally:
        tmp.cleanup()


def _run_e2e_compare(root, model, state, num_images, size, batch) -> dict:
    from batchai_retinanet_horovod_coco_tpu.data import (
        CocoDataset,
        PipelineConfig,
        build_pipeline,
        make_synthetic_coco,
    )
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        collect_detections,
        make_detect_fn,
        run_coco_eval,
    )

    make_synthetic_coco(
        root, num_images=num_images, num_classes=8,
        image_size=(size, size), seed=0,
    )
    ds = CocoDataset(
        os.path.join(root, "instances_train.json"),
        os.path.join(root, "train"),
    )
    pipe_cfg = PipelineConfig(
        batch_size=batch, buckets=((size, size),), min_side=size,
        max_side=size, max_gt=100, shuffle=False, hflip_prob=0.0,
        drop_remainder=False, num_workers=2,
    )
    # The untrained head's π=0.01 score prior sits below the production
    # 0.05 threshold, which would make both passes emit ZERO detections —
    # a vacuous bit-identity check and no host-side conversion/scoring
    # load at all.  A 0.001 threshold floods the consumer at
    # max_detections volume instead (an upper bound on trained-model host
    # load — the honest direction for a pipeline bench).
    cfg = DetectConfig(score_threshold=0.001)
    hw = (size, size)
    detect_fns = {hw: make_detect_fn(model, hw, cfg)}
    # Compile once OUTSIDE both timed passes.
    jax.device_get(
        detect_fns[hw](state, jnp.zeros((batch, size, size, 3), jnp.uint8))
    )

    def eval_pass(pipelined: bool) -> tuple[float, dict]:
        batches = build_pipeline(ds, pipe_cfg, train=False)
        try:
            with obs_trace.span("e2e_eval", pipelined=pipelined):
                t0 = time.perf_counter()
                metrics = run_coco_eval(
                    state, model, ds, batches, cfg,
                    pipelined=pipelined, detect_fns=detect_fns,
                )
                dt = time.perf_counter() - t0
            return dt, metrics
        finally:
            batches.close()

    def detect_pass(pipelined: bool) -> list[dict]:
        batches = build_pipeline(ds, pipe_cfg, train=False)
        try:
            return collect_detections(
                state, model, ds, batches, cfg,
                pipelined=pipelined, detect_fns=detect_fns,
            )
        finally:
            batches.close()

    t_seq, m_seq = eval_pass(False)
    t_pipe, m_pipe = eval_pass(True)
    dt_seq = detect_pass(False)
    bit_identical = dt_seq == detect_pass(True)
    return {
        "images": num_images,
        "bucket": f"{size}x{size}",
        "batch": batch,
        "score_threshold": cfg.score_threshold,
        "detections": len(dt_seq),
        "sequential_s": round(t_seq, 3),
        "pipelined_s": round(t_pipe, 3),
        "speedup": round(t_seq / max(t_pipe, 1e-9), 3),
        "bit_identical": bool(bit_identical),
        "map_equal": bool(m_seq == m_pipe),
    }


def run_eval_mode() -> None:
    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    measure_steps = int(os.environ.get("EVALBENCH_STEPS", str(MEASURE_STEPS)))
    # BENCH_SWEEP=0 skips the non-flagship buckets (same knob as train
    # mode); EVALBENCH_E2E=0 skips the minutes-long sequential-vs-
    # pipelined comparison.
    sweep = os.environ.get("BENCH_SWEEP", "1") not in ("", "0")
    with_e2e = os.environ.get("EVALBENCH_E2E", "1") not in ("", "0")
    model, state = _eval_model_and_state()
    device_kind = jax.devices()[0].device_kind

    # Per-bucket eval batch from the device's schedule when tuned
    # (tune/schedule.py); BENCH_BATCH (or the default 8) for untuned
    # buckets.  An explicit BENCH_BATCH env pins every bucket.
    from batchai_retinanet_horovod_coco_tpu.tune import eval_batch_for

    pinned = "BENCH_BATCH" in os.environ

    per_bucket: dict[str, dict] = {}
    value = None
    for hw, _share in sweep_buckets():
        if not sweep and hw != BUCKET:
            continue
        bucket_batch = (
            batch_size if pinned else eval_batch_for(hw, batch_size)
        )
        r = run_eval_bucket(model, state, bucket_batch, hw, measure_steps)
        per_bucket[f"{hw[0]}x{hw[1]}"] = r
        if hw == BUCKET:
            value = r["imgs_per_sec"]

    out = {
        "metric": "eval_images_per_sec_per_chip",
        "mode": "eval",
        "value": value,
        "unit": "images/sec/chip",
        "device_kind": device_kind,
        "measure_steps": measure_steps,
        "per_bucket": per_bucket,
        # Print a valid flagship record BEFORE the minutes-long e2e
        # comparison (same kill-safety contract as the train sweep).
    }
    att = _trace_attribution()
    if att is not None:
        out["attribution"] = att
    print(json.dumps(out), flush=True)
    if with_e2e:
        out["e2e"] = run_e2e_compare()
        # Re-derive: the e2e pass added the pipelined dispatch/fetch
        # spans the overlap ratio reads.
        att = _trace_attribution()
        if att is not None:
            out["attribution"] = att
        print(json.dumps(out))


# --- comm mode (ISSUE 13: the gradient-communication subsystem) -----------

# CPU-sized defaults: the comm bench runs on a FORCED virtual CPU mesh
# (COMMBENCH_DEVICES wide) — the measurands that matter are mesh-size
# arithmetic (bytes-on-wire ratio, static) and parity drift (numeric),
# which are device-independent; the step-time delta is recorded as
# indicative only (virtual-mesh collectives share one CPU).
COMM_DEVICES = 8
COMM_MEASURE_STEPS = 6
COMM_PARITY_STEPS = 10


def _comm_model_and_state():
    """Flagship topology at the dryrun's reduced width (the sharding and
    bucketing structure match the full model; CPU-compilable)."""
    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.train import create_train_state

    model = build_retinanet(
        RetinaNetConfig(
            num_classes=80, backbone="resnet50", dtype=jnp.float32,
            fpn_channels=64, head_width=64,
        )
    )
    state = create_train_state(
        model, optax.sgd(1e-2, momentum=0.9), (1, 64, 64, 3),
        jax.random.key(0),
    )
    return model, state


def _comm_batch(n: int, hw=(64, 64)):
    rng = np.random.default_rng(0)
    b = n
    return {
        "images": jnp.asarray(
            rng.normal(0, 1, (b, *hw, 3)).astype(np.float32)
        ),
        "gt_boxes": jnp.asarray(
            np.tile(
                np.array([[8.0, 8.0, 40.0, 40.0]], np.float32), (b, 1, 1)
            )
        ),
        "gt_labels": jnp.zeros((b, 1), np.int32),
        "gt_mask": jnp.ones((b, 1), bool),
    }


def _comm_timed_steps(step_fn, state, batch, steps: int) -> float:
    """Mean wall seconds/step with a hard scalar sync per step."""
    st = state
    st, m = step_fn(st, batch)
    float(m["loss"])  # warmup + sync
    t0 = time.perf_counter()
    for _ in range(steps):
        st, m = step_fn(st, batch)
    float(m["loss"])
    return (time.perf_counter() - t0) / max(1, steps)


def _comm_run_variant(
    model, state, mesh, n, batch, comm_cfg, steps, topology=None
):
    """(timed s/step, final state after COMM_PARITY_STEPS, losses)."""
    from batchai_retinanet_horovod_coco_tpu.comm import init_comm_state
    from batchai_retinanet_horovod_coco_tpu.train import make_train_step

    st = state
    if comm_cfg is not None and comm_cfg.needs_state:
        st = st.replace(
            comm_state=jax.device_put(
                init_comm_state(
                    state.params, comm_cfg, n, topology=topology
                )
            )
        )
    step_fn = make_train_step(
        model, (64, 64), 80, mesh=mesh, comm=comm_cfg, topology=topology,
        donate_state=False,
    )
    s_per_step = _comm_timed_steps(step_fn, st, batch, steps)
    losses = []
    for _ in range(COMM_PARITY_STEPS):
        st, m = step_fn(st, batch)
        losses.append(float(m["loss"]))
    return s_per_step, st, losses


def _param_rel_drift(a, b) -> float:
    num = 0.0
    den = 0.0
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        d = np.asarray(la, np.float64) - np.asarray(lb, np.float64)
        num += float(np.sum(d * d))
        den += float(np.sum(np.asarray(lb, np.float64) ** 2))
    return float(np.sqrt(num / max(den, 1e-30)))


def run_comm_record(sweep: bool) -> dict:
    """Measure the comm subsystem on a forced virtual CPU mesh: static
    bytes-on-wire vs exact, step-time delta, and parity drift after
    COMM_PARITY_STEPS identical steps (exact vs compressed)."""
    from __graft_entry__ import _force_virtual_cpu_mesh

    n = int(os.environ.get("COMMBENCH_DEVICES", str(COMM_DEVICES)))
    steps = int(os.environ.get("COMMBENCH_STEPS", str(COMM_MEASURE_STEPS)))
    _force_virtual_cpu_mesh(n)
    from batchai_retinanet_horovod_coco_tpu.comm import (
        CommConfig,
        plan_buckets,
    )
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh

    model, state = _comm_model_and_state()
    mesh = make_mesh(n)
    batch = _comm_batch(n)

    exact_s, exact_state, exact_losses = _comm_run_variant(
        model, state, mesh, n, batch, None, steps
    )

    variants = [("int8", CommConfig(compress="int8"))]
    if sweep:
        variants += [
            ("int8_overlap", CommConfig(compress="int8", overlap=True)),
            ("bf16", CommConfig(compress="bf16")),
            ("int8_bucket1mb", CommConfig(compress="int8", bucket_mb=1.0)),
        ]
    per_variant: dict[str, dict] = {}
    for name, cfg in variants:
        plan = plan_buckets(state.params, cfg)
        v_s, v_state, v_losses = _comm_run_variant(
            model, state, mesh, n, batch, cfg, steps
        )
        per_variant[name] = {
            "compressed_bytes": plan.compressed_bytes(n),
            "exact_bytes": plan.exact_bytes(n),
            "bytes_ratio": round(
                plan.compressed_bytes(n) / max(1, plan.exact_bytes(n)), 4
            ),
            "s_per_step": round(v_s, 4),
            "step_time_delta_pct": round(
                (v_s - exact_s) / max(exact_s, 1e-9) * 100, 2
            ),
            "loss_drift_at_n": round(
                abs(v_losses[-1] - exact_losses[-1])
                / max(abs(exact_losses[-1]), 1e-9),
                6,
            ),
            "param_rel_drift_at_n": round(
                _param_rel_drift(v_state.params, exact_state.params), 6
            ),
            "buckets": len(plan.buckets),
        }

    # Hierarchical leg (ISSUE 16): the same int8 policy routed through
    # the two-fabric tree on an EMULATED 2-slice topology (the virtual
    # CPU mesh playing S slices x L devices) — exact f32 within each
    # slice, quantization only on the cross-slice DCN hop.  Always
    # measured (not sweep-gated): commbench-check enforces the per-hop
    # claims.  Needs an even mesh; a deliberately odd COMMBENCH_DEVICES
    # records the skip instead of faking a topology.
    if n % 2 == 0 and n >= 4:
        from batchai_retinanet_horovod_coco_tpu.parallel import CommTopology

        topo = CommTopology(num_slices=2, slice_size=n // 2)
        hier_cfg = CommConfig(compress="int8")  # ici exact, dcn int8
        assert hier_cfg.hierarchical_with(topo)
        hier_mesh = make_mesh(n, topology=topo)
        hplan = plan_buckets(state.params, hier_cfg, topo)
        h_s, h_state, h_losses = _comm_run_variant(
            model, state, hier_mesh, n, batch, hier_cfg, steps,
            topology=topo,
        )
        hop = hplan.hop_bytes(topo)
        hop_exact = hplan.hop_bytes_exact(topo)
        hop_quant = hplan.hop_quant_bytes(topo)
        per_variant["hier_int8_dcn"] = {
            "topology": f"{topo.num_slices}x{topo.slice_size}",
            "hop_bytes": hop,
            "hop_bytes_exact": hop_exact,
            "hop_quant_bytes": hop_quant,
            # Headline per-hop claims: the DCN hop's bytes vs the
            # all-exact hierarchical tree, and zero quantized ICI bytes.
            "dcn_bytes_ratio": round(
                hop["dcn"] / max(1, hop_exact["dcn"]), 4
            ),
            "ici_quant_bytes": hop_quant["ici"],
            "s_per_step": round(h_s, 4),
            "step_time_delta_pct": round(
                (h_s - exact_s) / max(exact_s, 1e-9) * 100, 2
            ),
            "loss_drift_at_n": round(
                abs(h_losses[-1] - exact_losses[-1])
                / max(abs(exact_losses[-1]), 1e-9),
                6,
            ),
            "param_rel_drift_at_n": round(
                _param_rel_drift(h_state.params, exact_state.params), 6
            ),
            "buckets": len(hplan.buckets),
        }
    flag = per_variant["int8"]
    return {
        "bench": "commbench",
        "metric": "comm_bytes_on_wire_ratio",
        "mode": "comm",
        # Headline: the int8 plan's compressed/exact bytes ratio (lower
        # is better; the ROADMAP claim is <= 0.65).
        "value": flag["bytes_ratio"],
        "unit": "compressed/exact bytes (per-device ring estimate)",
        "device_kind": jax.devices()[0].device_kind,
        "devices": n,
        "measure_steps": steps,
        "parity_steps": COMM_PARITY_STEPS,
        "exact_s_per_step": round(exact_s, 4),
        "per_variant": per_variant,
        "note": (
            "virtual-CPU-mesh capture: bytes/parity are device-"
            "independent; s_per_step is indicative only (collectives "
            "share one CPU)"
        ),
    }


def check_comm_against_committed(record: dict) -> int:
    """commbench-check: bytes ratio must hold the <= 0.65 claim AND not
    regress vs the committed COMMBENCH.json (+0.02 absolute tolerance);
    parity drift must stay within 3x the committed drift (floor 2e-2) —
    quantization noise is seed-stable but not bit-stable across jax
    versions.  Same device-class guard policy as the other modes."""
    try:
        with open(_artifact_path("COMMBENCH.json")) as f:
            committed = json.load(f)
    except (OSError, ValueError) as e:
        print(f"# commbench-check: cannot read committed baseline: {e}")
        return 1
    rc = 0
    fresh = record["per_variant"]["int8"]
    if committed.get("device_kind") != record["device_kind"]:
        print(
            f"# commbench-check: committed artifact is for "
            f"{committed.get('device_kind')!r}, this run is "
            f"{record['device_kind']!r} — rates not comparable across "
            "device classes; re-capture (bytes/parity checks still run)"
        )
    ratio = float(fresh["bytes_ratio"])
    if ratio > 0.65:
        print(
            f"# commbench-check: bytes ratio {ratio} > 0.65 — the "
            "compression claim no longer holds: REGRESSION"
        )
        rc = 1
    committed_ratio = float(
        committed.get("per_variant", {}).get("int8", {}).get(
            "bytes_ratio", committed.get("value", 0.65)
        )
    )
    if ratio > committed_ratio + 0.02:
        print(
            f"# commbench-check: bytes ratio regressed "
            f"{committed_ratio} -> {ratio} (> +0.02): REGRESSION"
        )
        rc = 1
    committed_drift = float(
        committed.get("per_variant", {}).get("int8", {}).get(
            "param_rel_drift_at_n", 0.0
        )
    )
    drift = float(fresh["param_rel_drift_at_n"])
    ceiling = max(3 * committed_drift, 2e-2)
    if drift > ceiling:
        print(
            f"# commbench-check: parity drift {drift} > {ceiling} "
            f"(3x committed {committed_drift}, floor 2e-2): REGRESSION"
        )
        rc = 1
    # Hierarchical leg (ISSUE 16): the per-hop claims — the DCN hop's
    # compressed bytes hold <= 0.65x the all-exact hierarchical tree,
    # the ICI hops carry ZERO quantized bytes, and the parity drift vs
    # the exact flat tree stays in the same band as the flat variant.
    hier = record["per_variant"].get("hier_int8_dcn")
    if hier is None:
        print(
            "# commbench-check: no hierarchical leg in this run "
            "(odd COMMBENCH_DEVICES?) — per-hop claims unchecked: "
            "REGRESSION"
        )
        rc = 1
    else:
        dcn_ratio = float(hier["dcn_bytes_ratio"])
        if dcn_ratio > 0.65:
            print(
                f"# commbench-check: DCN bytes ratio {dcn_ratio} > 0.65 "
                "— the per-hop compression claim no longer holds: "
                "REGRESSION"
            )
            rc = 1
        if int(hier["ici_quant_bytes"]) != 0:
            print(
                f"# commbench-check: ICI hops carry "
                f"{hier['ici_quant_bytes']} quantized bytes (must be 0 "
                "— the fast wire stays exact): REGRESSION"
            )
            rc = 1
        committed_hier_drift = float(
            committed.get("per_variant", {}).get("hier_int8_dcn", {}).get(
                "param_rel_drift_at_n", 0.0
            )
        )
        hier_drift = float(hier["param_rel_drift_at_n"])
        hier_ceiling = max(3 * committed_hier_drift, 2e-2)
        if hier_drift > hier_ceiling:
            print(
                f"# commbench-check: hierarchical parity drift "
                f"{hier_drift} > {hier_ceiling} (3x committed "
                f"{committed_hier_drift}, floor 2e-2): REGRESSION"
            )
            rc = 1
    if rc == 0:
        print(
            f"# commbench-check: bytes ratio {ratio} <= 0.65 (committed "
            f"{committed_ratio}), parity drift {drift} <= {ceiling}, "
            f"DCN ratio {hier['dcn_bytes_ratio']} <= 0.65 with 0 "
            "quantized ICI bytes: ok"
        )
    return rc


def run_comm_mode() -> None:
    sweep = os.environ.get("BENCH_SWEEP", "1") not in ("", "0")
    record = run_comm_record(sweep)
    print(json.dumps(record), flush=True)
    out_path = os.environ.get("COMMBENCH_OUT")
    if out_path:
        from batchai_retinanet_horovod_coco_tpu.utils.atomicio import (
            atomic_write_text,
        )

        atomic_write_text(
            out_path, json.dumps(record, indent=2, sort_keys=True) + "\n"
        )
        print(f"# commbench record written to {out_path}", flush=True)
    if os.environ.get("BENCH_CHECK", "") not in ("", "0"):
        raise SystemExit(check_comm_against_committed(record))


# --- serve mode (ISSUE 4: the dynamic-batching inference server) ----------

# Timed dispatches per bucket; SERVEBENCH_STEPS overrides.
SERVE_MEASURE_STEPS = 30


def _serve_source_image(hw: tuple[int, int], min_side: int, max_side: int):
    """A source-resolution image that routes into ``hw`` with a NO-OP
    resize (min side exactly ``min_side``, max exactly ``max_side``), so
    the closed loop measures batching+dispatch, not cv2."""
    h, w = hw
    if h < w:
        shape = (min_side, max_side)
    elif h > w:
        shape = (max_side, min_side)
    else:
        shape = (min_side, min_side)
    rng = np.random.default_rng(2)
    return rng.integers(0, 256, (*shape, 3), dtype=np.uint8)


def _serve_ceiling(engine, hw, batch_size, steps) -> float:
    """In-run detect throughput ceiling on the SAME executable the server
    dispatches (run_eval_bucket's timing pattern: sequential dispatch,
    one hard sync per window) — the denominator of ``vs_ceiling``."""
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (batch_size, *hw, 3), dtype=np.uint8)
    det = engine.dispatch(hw, images)
    _sync_scalar(engine.fetch(det))
    t0 = time.perf_counter()
    for _ in range(steps):
        det = engine.dispatch(hw, images)
    _sync_scalar(engine.fetch(det))
    return batch_size * steps / (time.perf_counter() - t0)


def _serve_closed_loop(server, img, target: int, clients: int) -> dict:
    """Saturating closed loop: ``clients`` threads keep one request each
    in flight until ``target`` requests complete AFTER a one-batch warm
    period; returns steady-state imgs/s + the server's latency stats."""
    import threading

    from batchai_retinanet_horovod_coco_tpu.serve import (
        RequestRejected,
        ServeError,
    )

    stop = threading.Event()
    lock = threading.Lock()
    state = {"completed": 0, "shed": 0, "t_warm": None, "t_end": None,
             "errors": []}
    warm = max(1, clients)

    def client():
        try:
            _client_loop()
        except BaseException as e:
            # Crash channel (thread-error-contract): a silently-dead
            # client skews the closed-loop number, so the crash is
            # recorded and re-raised as a bench failure after the join.
            with lock:
                state["errors"].append(repr(e))
            stop.set()
            raise

    def _client_loop():
        while not stop.is_set():
            try:
                fut = server.submit(img)
            except RequestRejected:
                with lock:
                    state["shed"] += 1
                continue
            except ServeError:
                return
            try:
                fut.result(timeout=600)
            except ServeError:
                return
            except TimeoutError:
                stop.set()
                return
            now = time.perf_counter()
            with lock:
                state["completed"] += 1
                if state["completed"] == warm:
                    state["t_warm"] = now
                if state["completed"] >= warm + target:
                    state["t_end"] = now
                    stop.set()

    t0 = time.perf_counter()
    # watchdog-exempt: bench client threads, stop-event bounded.
    threads = [
        threading.Thread(target=client, daemon=True, name=f"bench-client-{i}")
        for i in range(clients)
    ]
    for t in threads:
        t.start()
    # Wake on target-reached OR every-client-dead (a crashed server ends
    # the clients without setting stop; never sleep out the full hour).
    while not stop.is_set() and any(t.is_alive() for t in threads):
        stop.wait(timeout=1.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    if state["errors"]:
        raise RuntimeError(
            f"bench client thread(s) crashed: {state['errors'][:3]}"
        )
    t_warm = state["t_warm"] or t0
    t_end = state["t_end"] or time.perf_counter()
    measured = max(0, state["completed"] - warm)
    dt = max(t_end - t_warm, 1e-9)
    snap = server.snapshot()
    return {
        "imgs_per_sec": round(measured / dt, 3),
        "completed": state["completed"],
        "closed_loop_shed": state["shed"],
        "clients": clients,
        "p50_ms": snap.get("p50_ms"),
        "p99_ms": snap.get("p99_ms"),
        "deadline_fires": snap.get("deadline_fires"),
    }


def _serve_overload(engine, hw, batch_size, img) -> dict:
    """Open-loop flood against tiny bounded queues: the evidence that
    overload SHEDS (bounded accepted set, bounded p99) instead of
    queueing unboundedly.  Every accepted request must resolve."""
    from batchai_retinanet_horovod_coco_tpu.serve import (
        DetectionServer,
        RequestRejected,
        ServeConfig,
    )

    admission = max(4, batch_size)
    bucket_q = max(2, batch_size // 2)
    server = DetectionServer(
        engine,
        ServeConfig(
            max_delay_ms=5.0,
            admission_queue=admission,
            bucket_queue=bucket_q,
            preprocess_workers=1,
        ),
        warmup=False,  # the ceiling measurement already warmed it
    )
    submissions = 6 * (admission + bucket_q)
    accepted, shed = [], 0
    try:
        for _ in range(submissions):
            try:
                accepted.append(server.submit(img))
            except RequestRejected:
                shed += 1
        resolved = sum(1 for f in accepted if f._event.wait(600))
        snap = server.snapshot()
    finally:
        server.close(drain=False)
    return {
        "submitted": submissions,
        "shed_at_submit": shed,
        "accepted": len(accepted),
        "resolved": resolved,
        "completed": snap["completed"],
        "shed_total": snap["shed_total"],
        "p99_ms": snap.get("p99_ms"),
        # The bounded-latency contract: nothing ever queued beyond the
        # configured bounds, and the flood was shed, not buffered.
        "sheds_instead_of_queueing": bool(
            shed > 0 and resolved == len(accepted)
        ),
    }


def _mixed_arrival_schedule(
    n: int, base_rate: float, seed: int = 0
) -> list[float]:
    """The seeded steady → burst → lull schedule, now the SHARED helper
    (ISSUE 18 satellite: utils/arrivals.py — the streaming leg composes
    multi-stream traces from the same seeded family, and unit tests pin
    determinism per seed there)."""
    from batchai_retinanet_horovod_coco_tpu.utils.arrivals import (
        mixed_arrival_schedule,
    )

    return mixed_arrival_schedule(n, base_rate, seed)


def _open_loop_leg(server, images: list, schedule: list[float]) -> dict:
    """Drive one server with the seeded open-loop schedule (request i =
    images[i % len] submitted at schedule[i]); returns p50/p99 over
    completed requests + the server's occupancy/fire counters, and the
    per-request results for the bit-identity cross-check."""
    from batchai_retinanet_horovod_coco_tpu.obs.events import (
        latency_percentiles,
    )
    from batchai_retinanet_horovod_coco_tpu.serve import RequestRejected

    import threading

    t0 = time.perf_counter()
    pending: list[tuple[int, float, object]] = []
    lock = threading.Lock()
    submitted = threading.Event()
    shed = [0]

    errors: list[str] = []

    def submit_on_schedule():
        try:
            for i, due in enumerate(schedule):
                delay = t0 + due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    fut = server.submit(images[i % len(images)])
                except RequestRejected:
                    with lock:
                        shed[0] += 1
                    continue
                with lock:
                    pending.append((i, time.perf_counter(), fut))
        except BaseException as e:
            # Crash channel (thread-error-contract): a dead submitter
            # skews the open-loop comparison — record and re-raise as a
            # bench failure after the join.
            with lock:
                errors.append(repr(e))
            raise
        finally:
            submitted.set()

    # watchdog-exempt: bench load generator, joined below.
    sub = threading.Thread(
        target=submit_on_schedule, daemon=True, name="bench-open-loop"
    )
    sub.start()
    # Collect CONCURRENTLY with submission, in submission order (batch
    # completion is FIFO here), so each latency is measured at the
    # moment its future resolves — not at drain time.
    latencies, results = [], {}
    j = 0
    while True:
        with lock:
            item = pending[j] if j < len(pending) else None
        if item is None:
            if submitted.is_set() and j >= len(pending):
                break
            time.sleep(0.002)
            continue
        i, t_sub, fut = item
        j += 1
        try:
            results[i] = fut.result(timeout=600)
        except Exception:
            with lock:
                shed[0] += 1
            continue
        latencies.append((time.perf_counter() - t_sub) * 1e3)
    sub.join(timeout=60)
    if errors:
        raise RuntimeError(f"open-loop submitter crashed: {errors}")
    shed = shed[0]
    snap = server.snapshot()
    pct = latency_percentiles(latencies, ps=(50, 99)) if latencies else {}
    return {
        "requests": len(schedule),
        "completed": len(latencies),
        "shed": shed,
        "p50_ms": pct.get("p50_ms"),
        "p99_ms": pct.get("p99_ms"),
        "occupancy_mean": snap.get("occupancy_mean"),
        "batches": snap.get("batches"),
        "deadline_fires": snap.get("deadline_fires"),
        "ready_fires": snap.get("ready_fires"),
        "full_fires": snap.get("full_fires"),
        "_results": results,
    }


def run_continuous_leg(
    make_engine,
    img_for,
    base_rate: float,
    n_requests: int,
    engine_kind: str,
    bit_check=None,
    seed: int = 0,
) -> dict:
    """The continuous-vs-deadline comparison (ISSUE 14): the SAME seeded
    open-loop mixed-arrival schedule against the SAME executable, once
    with the slot-pool dispatch gate (``continuous=True``) and once
    deadline-only.  The contract the fields state: continuous
    mean device batch occupancy strictly above deadline-only, p99 no
    worse (band), and — on the live-engine leg — served detections
    bit-identical to the sequential path on the same artifacts.

    ``make_engine()`` returns the (shared) engine per leg; ``img_for(i)``
    the i-th distinct request payload; ``bit_check(results, images)``
    the in-run sequential cross-check (live engine only).
    """
    from batchai_retinanet_horovod_coco_tpu.serve import (
        DetectionServer,
        ServeConfig,
    )

    n_imgs = 4
    images = [img_for(i) for i in range(n_imgs)]
    schedule = _mixed_arrival_schedule(n_requests, base_rate, seed)
    legs = {}
    for mode, continuous in (("deadline", False), ("continuous", True)):
        engine = make_engine()
        server = DetectionServer(
            engine,
            ServeConfig(
                max_delay_ms=10.0,
                continuous=continuous,
                preprocess_workers=2,
            ),
            warmup=False,
        )
        try:
            with obs_trace.span("serve_continuous_leg", mode=mode):
                legs[mode] = _open_loop_leg(server, images, schedule)
        finally:
            server.close(drain=False)
    out = {
        "engine": engine_kind,
        "requests": n_requests,
        "seed": seed,
        "base_rate_per_s": round(base_rate, 3),
        "deadline": {
            k: v for k, v in legs["deadline"].items() if k != "_results"
        },
        "continuous": {
            k: v for k, v in legs["continuous"].items() if k != "_results"
        },
    }
    d_occ = legs["deadline"]["occupancy_mean"] or 0.0
    c_occ = legs["continuous"]["occupancy_mean"] or 0.0
    out["occupancy_gain"] = round(c_occ - d_occ, 4)
    d99, c99 = legs["deadline"]["p99_ms"], legs["continuous"]["p99_ms"]
    if d99 and c99:
        out["p99_ratio"] = round(c99 / d99, 4)
    if bit_check is not None:
        out["bit_identical"] = bit_check(
            legs["continuous"]["_results"], images
        )
    return out


def run_continuous_leg_stub(seed: int = 0) -> dict:
    """The device-independent fast path (all that runs under
    ``SERVEBENCH_E2E=0``): the stub engine with injected device time, so
    the occupancy/p99 comparison runs on every box."""
    from batchai_retinanet_horovod_coco_tpu.serve.stub import (
        StubDetectEngine,
    )

    delay_s, batch = 0.03, 8
    capacity = batch / delay_s

    def img_for(i):
        rng = np.random.default_rng(100 + i)
        return rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)

    return run_continuous_leg(
        make_engine=lambda: StubDetectEngine(
            batch_sizes=(batch,), delay_s=delay_s
        ),
        img_for=img_for,
        base_rate=0.9 * capacity,
        n_requests=int(os.environ.get("SERVEBENCH_CONTINUOUS_N", "240")),
        engine_kind="stub",
        seed=seed,
    )


def run_continuous_leg_e2e(model, state, batch_size: int, seed: int = 0) -> dict:
    """The live-executable leg: flagship bucket,
    arrival rate derived from the in-run detect ceiling, plus the in-run
    bit-identity cross-check — each continuous-mode result compared
    against the SAME artifact driven sequentially (single-request
    assembly through ``assemble_requests`` + ``detections_to_coco``,
    exactly the serve conversion)."""
    import jax as _jax

    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        detections_to_coco,
    )
    from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
        bucket_for_source,
        resize_for_bucket,
    )
    from batchai_retinanet_horovod_coco_tpu.serve import DetectEngine
    from batchai_retinanet_horovod_coco_tpu.serve.batcher import (
        assemble_requests,
    )
    from batchai_retinanet_horovod_coco_tpu.serve.common import ServeRequest

    hw = BUCKET
    min_side, max_side = 800, 1333
    # Sub-prior threshold so the untrained head yields detections and
    # the bit-identity check cannot pass vacuously (the test-suite
    # policy, tests/unit/test_serve.py::_detect_config).
    config = DetectConfig(
        score_threshold=0.001, pre_nms_size=64, max_detections=10
    )
    engine = DetectEngine.from_state(
        model, state, buckets=(hw,), batch_sizes=(batch_size,),
        config=config, min_side=min_side, max_side=max_side,
    )
    engine.warmup()
    ceiling = _serve_ceiling(engine, hw, batch_size, 2)

    def img_for(i):
        h, w = hw
        shape = (
            (min_side, max_side) if h < w
            else (max_side, min_side) if h > w
            else (min_side, min_side)
        )
        rng = np.random.default_rng(100 + i)
        return rng.integers(0, 256, (*shape, 3), dtype=np.uint8)

    def bit_check(results: dict, images: list) -> bool:
        if not results:
            # Anti-vacuity (the sub-prior-threshold policy's sibling): a
            # leg that completed nothing verified nothing.
            print("# continuous-leg bit-identity VACUOUS: no completed "
                  "requests to compare", flush=True)
            return False
        ok = True
        for idx in sorted(set(i % len(images) for i in results)):
            img = images[idx]
            h, w = img.shape[:2]
            bucket = bucket_for_source(
                h, w, min_side, max_side, engine.buckets
            )
            resized, scale = resize_for_bucket(
                img, bucket, min_side, max_side
            )
            req = ServeRequest(0, None, None)
            req.image, req.scale = resized, np.float32(scale)
            req.orig_wh = (w, h)
            assembled = assemble_requests([req], bucket, batch_size)
            det = _jax.device_get(
                engine.dispatch(bucket, assembled.images)
            )
            want = detections_to_coco(
                det, np.array([0], np.int64), assembled.scales,
                assembled.valid, engine.label_to_cat_id,
                image_sizes={0: (w, h)},
            )
            for d in want:
                d.pop("image_id", None)
            got = [results[i] for i in results if i % len(images) == idx]
            if any(g != want for g in got):
                ok = False
                print(
                    f"# continuous-leg bit-identity MISMATCH on image "
                    f"{idx}", flush=True,
                )
        return ok

    n = int(os.environ.get(
        "SERVEBENCH_E2E_N", str(max(12, 3 * batch_size))
    ))
    return run_continuous_leg(
        make_engine=lambda: engine,
        img_for=img_for,
        base_rate=0.85 * ceiling,
        n_requests=n,
        engine_kind="live",
        bit_check=bit_check,
        seed=seed,
    )


def run_stream_leg(seed: int = 0) -> dict:
    """SERVEBENCH streaming leg (ISSUE 18): N seeded drift-footage
    streams replay a ``multi_stream_schedule`` arrival trace against the
    stub video engine WHILE a mixed single-image schedule rides the same
    server — one slot pool serving both client classes.  Reported:
    frames/sec, per-stream p99, cache hit rate, and the no-starvation
    evidence (every stream frame AND every single-image request
    completes).  Pure stub — device-independent, runs on every box."""
    import threading

    import numpy as np

    from batchai_retinanet_horovod_coco_tpu.serve import (
        DetectionServer,
        ServeConfig,
    )
    from batchai_retinanet_horovod_coco_tpu.serve.common import (
        RequestRejected,
        StreamConfig,
    )
    from batchai_retinanet_horovod_coco_tpu.serve.stream import StreamManager
    from batchai_retinanet_horovod_coco_tpu.serve.stub import (
        StubDetectEngine,
        drift_frames,
    )
    from batchai_retinanet_horovod_coco_tpu.utils.arrivals import (
        mixed_arrival_schedule,
        multi_stream_schedule,
    )

    n_streams = int(os.environ.get("SERVEBENCH_STREAMS", "3"))
    frames_per_stream = int(
        os.environ.get("SERVEBENCH_STREAM_FRAMES", "60")
    )
    fps = float(os.environ.get("SERVEBENCH_STREAM_FPS", "30"))
    n_single = int(os.environ.get("SERVEBENCH_STREAM_SINGLES", "40"))
    delta_threshold = 2.0
    engine = StubDetectEngine(batch_sizes=(8,), delay_s=0.01, video=True)
    server = DetectionServer(
        engine, ServeConfig(max_delay_ms=5.0), warmup=False
    )
    manager = StreamManager(
        server, StreamConfig(delta_threshold=delta_threshold)
    )
    schedules = multi_stream_schedule(
        n_streams, frames_per_stream, fps, seed=seed
    )
    # step 1.0 under threshold 2.0 = hits; a cut every 10 frames forces
    # periodic misses — both cache paths exercised in every capture.
    footage = [
        drift_frames(
            seed=seed + 10 * k, n=frames_per_stream, step=1.0,
            cut_every=10,
        )
        for k in range(n_streams)
    ]
    single_schedule = mixed_arrival_schedule(n_single, base_rate=40.0,
                                             seed=seed + 999)
    rng = np.random.default_rng(seed + 500)
    single_imgs = [
        rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)
        for _ in range(4)
    ]

    stream_stats: list[dict | None] = [None] * n_streams
    singles_done = [0]
    errors: list[str] = []
    t0 = time.perf_counter()

    def stream_client(k: int) -> None:
        try:
            sid = manager.open_stream(width=64, height=64)["session"]
            futs = []
            for i, at in enumerate(schedules[k]):
                now = time.perf_counter() - t0
                if at > now:
                    time.sleep(at - now)
                while True:
                    try:
                        futs.append(
                            manager.submit_frame(
                                sid, i, footage[k][i], timeout_s=30.0
                            )
                        )
                        break
                    except RequestRejected as exc:
                        if exc.reason != "stream_backlogged":
                            raise
                        time.sleep(0.002)  # open-loop slip, not a drop
            for f in futs:
                f.result(timeout=30.0)
            stream_stats[k] = manager.close_stream(sid)
        except Exception as e:
            errors.append(f"stream {k}: {e!r}")

    def single_client() -> None:
        try:
            futs = []
            for i, at in enumerate(single_schedule):
                now = time.perf_counter() - t0
                if at > now:
                    time.sleep(at - now)
                try:
                    futs.append(
                        server.submit(
                            single_imgs[i % len(single_imgs)],
                            timeout_s=30.0,
                        )
                    )
                except RequestRejected:
                    continue  # shed = load signal, not starvation
            for f in futs:
                f.result(timeout=30.0)
            singles_done[0] = len(futs)
        except Exception as e:
            errors.append(f"single-image client: {e!r}")

    # watchdog: bench-local load generators, bounded by the join below.
    threads = [
        threading.Thread(target=stream_client, args=(k,), daemon=True)
        for k in range(n_streams)
    ] + [threading.Thread(target=single_client, daemon=True)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    wall_s = time.perf_counter() - t0
    status = manager.status()
    manager.close()
    server.close(drain=False)
    if errors:
        raise RuntimeError(f"stream leg clients failed: {errors}")

    frames_total = sum(s["frames"] for s in stream_stats if s)
    hits = sum(s["cache_hits"] for s in stream_stats if s)
    per_stream_p99 = [
        s.get("p99_ms") for s in stream_stats if s and s.get("p99_ms")
    ]
    return {
        "engine": "stub",
        "seed": seed,
        "streams": n_streams,
        "frames_per_stream": frames_per_stream,
        "fps": fps,
        "frames_total": frames_total,
        "dropped": n_streams * frames_per_stream - frames_total,
        "frames_per_sec": round(frames_total / wall_s, 2),
        "cache_hit_rate": round(hits / max(1, frames_total), 4),
        "cache_bytes_saved": status["cache_bytes_saved"],
        "per_stream_p99_ms": per_stream_p99,
        "p99_ms_max": max(per_stream_p99) if per_stream_p99 else None,
        "single_image": {
            "requests": n_single,
            "completed": singles_done[0],
        },
    }


def run_autoscale_leg(seed: int = 0) -> dict:
    """SERVEBENCH autoscale leg (ISSUE 19): a seeded diurnal day with
    one rush-hour spike replays through the REAL control plane —
    FleetRouter + Autoscaler + LocalLauncher over in-process stub
    replicas.  The record states the elasticity contract: the
    fleet grows under the spike (>=1 scale-up, peak >= 2 replicas), p99
    holds through it, every request resolves (zero drops — scale-down
    drains are invisible to clients), and the fleet returns to
    ``min_replicas`` once the day quiets.  Pure stub —
    device-independent, runs (and is checked) on every box."""
    import threading

    import numpy as np

    from batchai_retinanet_horovod_coco_tpu.serve import (
        AutoscalePolicy,
        Autoscaler,
        DetectionServer,
        FleetConfig,
        FleetRouter,
        LocalLauncher,
        LocalReplica,
        RequestRejected,
        ServeConfig,
        ServeError,
    )
    from batchai_retinanet_horovod_coco_tpu.serve.stub import (
        StubDetectEngine,
    )
    from batchai_retinanet_horovod_coco_tpu.utils.arrivals import (
        diurnal_spike_schedule,
    )

    n = int(os.environ.get("SERVEBENCH_AUTOSCALE_REQUESTS", "240"))
    base_rate = float(os.environ.get("SERVEBENCH_AUTOSCALE_RATE", "12"))
    clients = 16

    def factory(rid):
        server = DetectionServer(
            StubDetectEngine(delay_s=0.06),
            ServeConfig(max_delay_ms=2.0, preprocess_workers=1),
            replica_id=rid,
        )
        return LocalReplica(server)

    launcher = LocalLauncher(
        factory, drain_timeout_s=15.0, prefix="bench-scale"
    )
    seed_replica = factory("bench-scale-seed")
    launcher.adopt(seed_replica)
    router = FleetRouter(
        [seed_replica],
        FleetConfig(poll_interval_s=0.1, default_timeout_s=30.0),
    )
    # The chaos.py --autoscale leg proved this band/cadence against the
    # same 60 ms stub: off-peak sits inside the band, the 4x spike
    # breaches high, the post-day quiet breaches low back to min.
    policy = AutoscalePolicy(
        min_replicas=1, max_replicas=3,
        occupancy_low=0.15, occupancy_high=0.5,
        for_s=0.4, up_cooldown_s=1.0, down_cooldown_s=2.0,
        interval_s=0.1,
    )
    scaler = Autoscaler(router, policy, launcher).start()

    times = diurnal_spike_schedule(
        n, base_rate=base_rate, seed=seed, period_s=12.0,
        amplitude=0.5, spikes=((0.55, 0.4, 4.0),),
    )
    img = np.zeros((64, 64, 3), np.uint8)
    lock = threading.Lock()
    next_i = [0]
    latencies: list[float] = []
    counts = {"ok": 0, "shed": 0, "dropped": 0}

    def client():
        try:
            while True:
                with lock:
                    i = next_i[0]
                    if i >= len(times):
                        return
                    next_i[0] += 1
                wait = times[i] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)  # open-loop pacing; busy = slip
                t1 = time.perf_counter()
                try:
                    router.detect(img)
                    with lock:
                        counts["ok"] += 1
                        latencies.append(
                            (time.perf_counter() - t1) * 1e3
                        )
                except RequestRejected:
                    with lock:
                        counts["shed"] += 1
                except ServeError:
                    with lock:
                        counts["dropped"] += 1
        except Exception as e:  # crash channel: an unresolved request
            print(f"# autoscale leg client crashed: {e!r}", flush=True)
            with lock:
                counts["dropped"] += 1
            raise

    # watchdog: bench-local load generators, bounded by the join below.
    threads = [
        threading.Thread(target=client, daemon=True)
        for _ in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()

    # Sampler doubles as the join loop: the replica-count trajectory vs
    # offered load is the record's elasticity evidence.
    trajectory: list[list[float]] = []
    deadline = t0 + times[-1] + 120.0
    while any(t.is_alive() for t in threads):
        if time.perf_counter() > deadline:
            break
        with lock:
            offered = next_i[0]
        trajectory.append([
            round(time.perf_counter() - t0, 2),
            float(offered),
            float(router.active_replica_count()),
        ])
        time.sleep(0.5)
    for t in threads:
        t.join(timeout=10)
    hung = sum(t.is_alive() for t in threads)

    # The day is over: wait for the scale-down half of the contract —
    # the quiet fleet drains back to min_replicas with zero drops.
    quiet_deadline = time.perf_counter() + 60.0
    while time.perf_counter() < quiet_deadline:
        st = scaler.status()
        if (router.active_replica_count() <= policy.min_replicas
                and st["scale_downs"] >= 1 and not st["draining"]):
            break
        trajectory.append([
            round(time.perf_counter() - t0, 2),
            float(n),
            float(router.active_replica_count()),
        ])
        time.sleep(0.25)
    final_replicas = router.active_replica_count()
    st = scaler.status()
    decisions = [
        {"decision": d["decision"], "reason": d["reason"],
         "delta": d["delta"]}
        for d in scaler.decisions
    ]
    scaler.stop()
    router.close(close_replicas=True)

    peak = max([s[2] for s in trajectory] or [1.0])
    p99 = (
        round(float(np.percentile(np.asarray(latencies), 99)), 2)
        if latencies else None
    )
    return {
        "engine": "stub",
        "seed": seed,
        "requests": n,
        "completed": counts["ok"],
        "shed": counts["shed"],
        "dropped": counts["dropped"] + hung,
        "p99_ms": p99,
        "scaled_up": st["scale_ups"],
        "scaled_down": st["scale_downs"],
        "capped": st["capped"],
        "peak_replicas": int(peak),
        "final_replicas": int(final_replicas),
        "min_replicas": policy.min_replicas,
        "max_replicas": policy.max_replicas,
        "decisions": decisions,
        # Downsampled so the record stays readable.
        "trajectory": trajectory[::2],
    }


def _scrape_telemetry(server) -> dict:
    """Scrape the live-telemetry plane ONCE per measurement window
    (ISSUE 9 satellite): mount the real HTTP frontend over the just-
    measured server, GET /metrics + /healthz, and cross-check the
    registry-derived p99/shed/completed numbers against the server's own
    snapshot.  The two sources read the SAME LatencyStats window through
    different code paths (Prometheus encode → text → parse vs direct
    snapshot), so any disagreement is a real exposition bug —
    ``consistent`` is recorded in the bench line and announced, never
    silently dropped."""
    import threading
    import urllib.error
    import urllib.request

    from batchai_retinanet_horovod_coco_tpu.obs import telemetry, watchdog
    from batchai_retinanet_horovod_coco_tpu.serve import serve_http

    httpd = serve_http(server, port=0)
    hb = watchdog.register("bench-telemetry-scrape")
    thread = threading.Thread(
        # Stdlib target: crashes surface as the scrape's urlopen failure.
        target=httpd.serve_forever, kwargs={"poll_interval": 0.05},
        daemon=True, name="bench-telemetry-scrape",
    )
    thread.start()
    try:
        host, port = httpd.server_address[:2]
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=30
        ) as r:
            text = r.read().decode()
        try:
            with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=30
            ) as r:
                health_code = r.status
                health = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:  # 503 = stalled (still data)
            health_code = e.code
            health = json.loads(e.read().decode())
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=10)
        hb.close()

    types, samples = telemetry.parse_exposition(text)
    snap = server.snapshot()
    p99 = samples.get('serve_request_latency_ms{quantile="0.99"}')
    shed = sum(
        v for k, v in samples.items() if k.startswith("serve_shed_total")
    )
    completed = samples.get("serve_requests_completed_total")
    problems = []
    if types.get("serve_request_latency_ms") != "summary":
        problems.append("latency family missing/untyped")
    if completed != snap["completed"]:
        problems.append(
            f"completed {completed} != snapshot {snap['completed']}"
        )
    if shed != snap["shed_total"]:
        problems.append(f"shed {shed} != snapshot {snap['shed_total']}")
    snap_p99 = snap.get("p99_ms")
    if (p99 is None) != (snap_p99 is None):
        problems.append(f"p99 presence mismatch ({p99} vs {snap_p99})")
    elif p99 is not None and abs(p99 - snap_p99) > max(0.5, 0.01 * snap_p99):
        problems.append(f"p99 {p99} != snapshot {snap_p99}")
    if problems:
        print(f"# telemetry-consistency MISMATCH: {problems}", flush=True)
    return {
        "registry_p99_ms": p99,
        "registry_shed_total": shed,
        "registry_completed": completed,
        "healthz_status": health_code,
        "healthz_ok": health_code == 200 and health.get("status") == "ok",
        "consistent": not problems,
    }


def run_serve_bucket(
    model, state, batch_size: int, hw: tuple[int, int], measure_steps: int,
    overload: bool,
) -> dict:
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
    )
    from batchai_retinanet_horovod_coco_tpu.serve import (
        DetectEngine,
        DetectionServer,
        ServeConfig,
    )

    min_side, max_side = 800, 1333  # the flagship resize rule behind BUCKET
    engine = DetectEngine.from_state(
        model, state, buckets=(hw,), batch_sizes=(batch_size,),
        config=DetectConfig(), min_side=min_side, max_side=max_side,
    )
    engine.warmup()
    ceiling = _serve_ceiling(
        engine, hw, batch_size, max(1, measure_steps // 2)
    )
    img = _serve_source_image(hw, min_side, max_side)
    server = DetectionServer(
        engine,
        ServeConfig(
            max_delay_ms=10.0,
            admission_queue=4 * batch_size,
            bucket_queue=4 * batch_size,
            preprocess_workers=2,
        ),
        warmup=False,
    )
    try:
        closed = _serve_closed_loop(
            server, img,
            target=measure_steps * batch_size,
            clients=max(2, 2 * batch_size),
        )
        # One /metrics scrape per window, against the still-open server
        # (the closed loop has joined its clients, so the stats are
        # frozen and the two sources must agree exactly).
        telem = _scrape_telemetry(server)
    finally:
        server.close(drain=False)
    out = {
        "batch": batch_size,
        "detect_ceiling_imgs_per_sec": round(ceiling, 3),
        "vs_ceiling": round(closed["imgs_per_sec"] / max(ceiling, 1e-9), 3),
        **closed,
        "telemetry": telem,
    }
    if overload:
        with obs_trace.span("serve_overload", bucket=f"{hw[0]}x{hw[1]}"):
            out["overload"] = _serve_overload(engine, hw, batch_size, img)
    return out


# ---------------------------------------------------------------------------
# Fleet availability leg (ISSUE 12): real fleet machinery, stub replicas
# ---------------------------------------------------------------------------


def run_fleet_leg() -> dict:
    """Kill-a-replica availability + canary rollback on the REAL fleet
    router (serve/fleet.py) over in-process stub replicas.

    No device work at all — the measurand is the ROUTER's mechanics
    (availability under replica death, bounded re-dispatch, exactly-once
    canary rollback), which are device-independent, so the leg runs
    identically on the chip and on a CPU box.  The contract the
    ``fleet`` fields state: every submitted request RESOLVES
    (availability 1.0 — completes or sheds with a reason, zero hangs),
    and post-kill completion stays at or above the surviving capacity
    share ((N-1)/N).
    """
    import threading

    import numpy as np

    from batchai_retinanet_horovod_coco_tpu.serve import (
        DetectionServer,
        FleetConfig,
        FleetRouter,
        LocalReplica,
        RequestRejected,
        RequestTimeout,
        ServeConfig,
        ServeError,
    )
    from batchai_retinanet_horovod_coco_tpu.serve.stub import (
        StubDetectEngine,
    )

    n_replicas = 3
    servers = [
        DetectionServer(
            StubDetectEngine(delay_s=0.01),
            ServeConfig(max_delay_ms=2.0, preprocess_workers=1),
            replica_id=f"bench-r{i}",
        )
        for i in range(n_replicas)
    ]
    router = FleetRouter(
        [LocalReplica(s) for s in servers],
        FleetConfig(
            poll_interval_s=0.05, default_timeout_s=20.0,
            canary_weight=0.5, canary_p99_factor=3.0,
            canary_for_s=0.2, canary_poll_s=0.05,
        ),
    )
    img = np.zeros((64, 64, 3), np.uint8)
    total, clients = 120, 4
    kill_at = total // 2
    lock = threading.Lock()
    counts = {"ok": 0, "shed": 0, "timeout": 0, "failed": 0}
    post_kill = {"ok": 0, "total": 0}
    issued = [0]
    killed = [False]

    def client():
        try:
            while True:
                with lock:
                    if issued[0] >= total:
                        return
                    issued[0] += 1
                    fire = issued[0] == kill_at and not killed[0]
                    if fire:
                        killed[0] = True
                if fire:
                    # The in-process SIGKILL equivalent: the victim's
                    # threads stop and every subsequent submit raises —
                    # the router must breaker it and re-dispatch.
                    servers[0].close(drain=False)
                try:
                    router.detect(img)
                    out = "ok"
                except RequestRejected:
                    out = "shed"
                except RequestTimeout:
                    out = "timeout"
                except ServeError:
                    out = "failed"
                with lock:
                    counts[out] += 1
                    if killed[0]:
                        post_kill["total"] += 1
                        post_kill["ok"] += out == "ok"
        except Exception as e:  # crash channel: an unresolved request
            print(f"# fleet leg client crashed: {e!r}", flush=True)
            raise

    # watchdog: bench-local load generators, bounded by the join below.
    threads = [
        threading.Thread(target=client, daemon=True) for _ in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)

    # Canary micro-leg: a visibly slow canary joins; its monitor (real
    # poll thread, aggressive cadence) must fire exactly one rollback.
    # 250 ms of injected device time: the serve stack's light-load
    # latency floor (~60 ms — the dispatcher's idle-flush poll) would
    # mask a smaller regression under the 3x ratio gate.
    canary_server = DetectionServer(
        StubDetectEngine(delay_s=0.25),
        ServeConfig(max_delay_ms=2.0, preprocess_workers=1),
        replica_id="bench-canary",
    )
    router.add_canary(LocalReplica(canary_server), start_monitor=True)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            router.detect(img)
        except ServeError:
            pass
        if router.status()["canary_rollbacks"] >= 1:
            break
    status = router.status()

    # Federated-vs-local p99 consistency (ISSUE 15): with traffic
    # quiesced, one federation sweep — each surviving replica's OWN
    # windowed p99 must round-trip the fleet scrape EXACTLY (both sides
    # read the same LatencyStats window through the same percentile
    # helper; any delta means federation re-labeled or lost samples).
    router.scrape_metrics_once()
    fed = router.federated_snapshot()
    fed_checked = 0
    fed_max_delta = 0.0
    fed_consistent = True
    for s in servers[1:]:  # servers[0] was killed mid-leg
        local = s.telemetry.snapshot().get("serve_request_latency_ms.p99")
        if local is None:
            continue
        fed_p99 = fed.get(
            "serve_request_latency_ms"
            f'{{quantile="0.99",replica="{s.replica_id}"}}'
        )
        fed_checked += 1
        if fed_p99 is None:
            fed_consistent = False
            print(
                f"# fleet leg: replica {s.replica_id} missing from the "
                "federated scrape", flush=True,
            )
            continue
        delta = abs(float(fed_p99) - float(local))
        fed_max_delta = max(fed_max_delta, delta)
        if delta > 1e-9:
            fed_consistent = False
            print(
                f"# fleet leg: federated p99 {fed_p99} != local {local} "
                f"on {s.replica_id}", flush=True,
            )

    router.close()
    for s in servers:
        s.close(drain=False)
    canary_server.close(drain=False)

    resolved = sum(counts.values())
    return {
        "replicas": n_replicas,
        "requests": issued[0],
        "completed": counts["ok"],
        "shed": counts["shed"],
        "timeout": counts["timeout"],
        "failed": counts["failed"],
        "unresolved": issued[0] - resolved,
        # THE availability claim: 1.0 = every request completed or shed
        # with a reason — nothing hung, nothing silently dropped.
        "availability": round(resolved / max(1, issued[0]), 4),
        "post_kill_ok_ratio": round(
            post_kill["ok"] / max(1, post_kill["total"]), 4
        ),
        "capacity_share_floor": round((n_replicas - 1) / n_replicas, 4),
        "redispatches": status["redispatches"],
        "breaker_opens": status["breaker_opens"],
        "canary_rollbacks": status["canary_rollbacks"],
        # Metrics-federation consistency (ISSUE 15): the fleet-scraped,
        # replica-labeled p99 equals each surviving replica's own
        # registry value on a quiesced fleet.
        "federated_p99_consistent": fed_consistent and fed_checked > 0,
        "federated_replicas_checked": fed_checked,
        "federated_p99_max_delta_ms": round(fed_max_delta, 6),
    }


def run_serve_mode() -> None:
    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    measure_steps = int(
        os.environ.get("SERVEBENCH_STEPS", str(SERVE_MEASURE_STEPS))
    )
    sweep = os.environ.get("BENCH_SWEEP", "1") not in ("", "0")
    overload = os.environ.get("SERVEBENCH_OVERLOAD", "1") not in ("", "0")
    model, state = _eval_model_and_state()
    device_kind = jax.devices()[0].device_kind

    per_bucket: dict[str, dict] = {}
    value = None
    for hw, _share in sweep_buckets():
        if not sweep and hw != BUCKET:
            continue
        r = run_serve_bucket(
            model, state, batch_size, hw, measure_steps, overload
        )
        per_bucket[f"{hw[0]}x{hw[1]}"] = r
        if hw == BUCKET:
            value = r["imgs_per_sec"]

    out = {
        "metric": "serve_images_per_sec_per_chip",
        "mode": "serve",
        "value": value,
        "unit": "images/sec/chip",
        "device_kind": device_kind,
        "measure_steps": measure_steps,
        "per_bucket": per_bucket,
    }
    # Fleet availability leg (ISSUE 12): stub-based (device-independent),
    # cheap — on by default; SERVEBENCH_FLEET=0 skips it.
    if os.environ.get("SERVEBENCH_FLEET", "1") not in ("", "0"):
        out["fleet"] = run_fleet_leg()
    # Continuous-vs-deadline leg (ISSUE 14): the same seeded open-loop
    # mixed-arrival schedule against the same executable in both
    # batching modes.  SERVEBENCH_E2E=1 (default) also runs it on the
    # live flagship executable with the in-run bit-identity cross-check;
    # SERVEBENCH_E2E=0 runs the device-independent stub leg only.
    # SERVEBENCH_CONTINUOUS=0 skips.
    if os.environ.get("SERVEBENCH_CONTINUOUS", "1") not in ("", "0"):
        with obs_trace.span("serve_continuous_vs_deadline"):
            cont = run_continuous_leg_stub()
            if os.environ.get("SERVEBENCH_E2E", "1") not in ("", "0"):
                cont["e2e"] = run_continuous_leg_e2e(
                    model, state, batch_size
                )
        out["continuous"] = cont
    # Streaming leg (ISSUE 18): seeded drift streams + mixed single-image
    # traffic through StreamManager over the stub video engine —
    # device-independent.  SERVEBENCH_STREAM=0 skips.
    if os.environ.get("SERVEBENCH_STREAM", "1") not in ("", "0"):
        with obs_trace.span("serve_stream_leg"):
            out["stream"] = run_stream_leg()
    # Autoscale leg (ISSUE 19): the seeded diurnal/spike day through the
    # real control plane (FleetRouter + Autoscaler) over stub replicas —
    # device-independent.  SERVEBENCH_AUTOSCALE=0 skips.
    if os.environ.get("SERVEBENCH_AUTOSCALE", "1") not in ("", "0"):
        with obs_trace.span("serve_autoscale_leg"):
            out["autoscale"] = run_autoscale_leg()
    att = _trace_attribution()
    if att is not None:
        out["attribution"] = att
    print(json.dumps(out), flush=True)


def run_train_mode() -> None:
    batch_size = int(os.environ.get("BENCH_BATCH", "8"))
    sweep = os.environ.get("BENCH_SWEEP", "1") not in ("", "0")
    # BENCH_STEPS: train-mode twin of EVALBENCH_STEPS/SERVEBENCH_STEPS.
    measure_steps = int(os.environ.get("BENCH_STEPS", str(MEASURE_STEPS)))

    ips, mfu, windows = run_bench(batch_size, BUCKET, measure_steps)
    value = round(ips, 3)
    out = {
        "metric": "train_images_per_sec_per_chip",
        "value": value,
        "unit": "images/sec/chip",
        # The device the number belongs to: a consumer must be able to
        # tell a chip run from one that landed on the CPU.
        "device_kind": jax.devices()[0].device_kind,
        "mfu": round(mfu, 4) if mfu is not None else None,
        # Same-run noise floor: two disjoint timed windows of the same
        # compiled step.  A cross-round delta inside this spread is noise.
        "window_rates": [round(w, 3) for w in windows],
        "noise_pct": round(
            abs(windows[0] - windows[1]) / value * 100, 2
        ),
    }
    # Which kernel schedule produced this number (tune/): the registry
    # artifact the step's kernel params resolved from, or the built-in
    # defaults on an untuned device.
    from batchai_retinanet_horovod_coco_tpu.tune import provenance

    out["schedule"] = provenance(out["device_kind"])

    # Numerics-plane overhead evidence (ISSUE 10): re-measure the SAME
    # flagship config with the in-step summary fused in and state the
    # on-vs-off delta in the JSON line.  BENCH_NUMERICS=0 skips (the
    # extra AOT compile is minutes on CPU).
    if os.environ.get("BENCH_NUMERICS", "1") not in ("", "0"):
        ips_on, _mfu_on, _win_on = run_bench(
            batch_size, BUCKET, measure_steps, numerics=True
        )
        out["numerics_overhead"] = {
            "imgs_per_sec_off": value,
            "imgs_per_sec_on": round(ips_on, 3),
            "delta_pct": round((value - ips_on) / value * 100, 2),
            "note": (
                "in-step numerics summary (obs/numerics.py) on vs off; "
                "delta within noise_pct is noise.  Disabled path is "
                "structurally free (identical compiled step)"
            ),
        }

    att = _trace_attribution()
    if att is not None:
        out["attribution"] = att
    if sweep:
        # Print the flagship-only line BEFORE the (minutes-long) sweep of
        # the other buckets: a consumer that reads the LAST line gets the
        # full sweep result, while a harness that kills the process on a
        # timeout still finds a complete, valid flagship line.
        print(json.dumps(out), flush=True)
        buckets = sweep_buckets()
        per_bucket = {f"{BUCKET[0]}x{BUCKET[1]}": value}
        rates = {BUCKET: ips}
        for hw, _share in buckets:
            if hw == BUCKET:
                continue
            b_ips, _b_mfu, _b_windows = run_bench(
                batch_size, hw, min(SWEEP_MEASURE_STEPS, measure_steps)
            )
            rates[hw] = b_ips
            per_bucket[f"{hw[0]}x{hw[1]}"] = round(b_ips, 3)
        # Mix-weighted throughput: steps are drawn per bucket with the
        # COCO aspect shares, so the average COST per image is the
        # share-weighted mean of 1/rate (harmonic mix), not of the rates.
        total_share = sum(s for _, s in buckets)
        cost = sum(s / rates[hw] for hw, s in buckets) / total_share
        out["per_bucket"] = per_bucket
        out["weighted_mix"] = round(1.0 / cost, 3)
        out["mix_shares"] = {
            f"{hw[0]}x{hw[1]}": s for hw, s in buckets
        }
        att = _trace_attribution()  # now includes the sweep buckets' spans
        if att is not None:
            out["attribution"] = att

    print(json.dumps(out))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--mode", choices=("train", "eval", "serve", "comm"),
        default="train",
        help="train = flagship SPMD train step; eval = detect/NMS fast "
             "path (per-bucket AOT detect + postprocess-only + "
             "sequential-vs-pipelined e2e); serve = dynamic-batching "
             "inference server (serve/) under a saturating closed loop "
             "+ an overload shed leg, vs the in-run detect ceiling; "
             "comm = gradient-compression subsystem (comm/) on a "
             "forced virtual CPU mesh — bytes-on-wire vs exact, "
             "step-time delta, parity drift (COMMBENCH.json)",
    )
    ap.add_argument(
        "--trace", "--obs-trace", action="store_true", dest="trace",
        help="record obs trace spans (AOT compiles, timed windows, and "
             "for --mode eval the full three-stage e2e pipeline) and "
             "write a Perfetto-loadable Chrome trace artifact per bench "
             "mode into --obs-dir (--obs-trace is the train.py spelling, "
             "accepted here too)",
    )
    ap.add_argument(
        "--obs-dir", default="artifacts/obs",
        help="where --trace writes its trace artifact",
    )
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.configure(
            args.obs_dir, process_label=f"bench-{args.mode}"
        )

    from batchai_retinanet_horovod_coco_tpu.utils.backend import (
        announce_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    if args.mode != "comm":
        # comm mode forces its own virtual CPU mesh, which must happen
        # before the backend initializes (run_comm_record).
        announce_devices("bench")

    try:
        if args.trace:
            # Device metadata into the trace: the perf report resolves
            # device_kind — hence the MFU peak — from the trace alone.
            obs_trace.instant(
                "run_meta", device_kind=jax.devices()[0].device_kind
            )
        if args.mode == "eval":
            run_eval_mode()
        elif args.mode == "serve":
            run_serve_mode()
        elif args.mode == "comm":
            run_comm_mode()
        else:
            run_train_mode()
    finally:
        if args.trace:
            obs_trace.export()
            merged = obs_trace.merge_traces(
                out_name=f"bench_{args.mode}_trace.json"
            )
            # "#"-prefixed: the bench's stdout contract is JSON lines plus
            # comment lines; a consumer parsing first/last JSON is safe.
            print(f"# trace written to {merged}", flush=True)
            # Perf-doctor report next to the trace (never raises — a
            # failed analysis is one structured stderr line, not a bench
            # failure).
            try:
                from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
                    auto_emit,
                )

                # events_name=None: bench writes no events JSONL, and a
                # shared obs dir may hold a previous TRAIN run's
                # metrics.jsonl — its header/compile/stall records must
                # not be attributed to this bench.
                report = auto_emit(
                    args.obs_dir,
                    trace_name=f"bench_{args.mode}_trace.json",
                    out_name=f"PERF_REPORT_bench_{args.mode}.json",
                    events_name=None,
                )
            except Exception as e:
                print(f"# perf report failed: {e!r}", flush=True)
                report = None
            if report:
                print(f"# perf report written to {report}", flush=True)


if __name__ == "__main__":
    main()
