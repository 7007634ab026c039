"""Timed schedule search (ISSUE 6): measure candidates, compose a winner.

The TVM lesson (PAPERS.md) applied at this repo's scale: the tunable
hot-path parameters — Pallas tile/block shapes for focal, matching and
NMS, ``pre_nms_size``, per-bucket batch sizes — are cheap enough to
search EXHAUSTIVELY (tune/candidates.py's menus are a handful of entries
each), so the harness is a measured argmin, not a learned cost model.

Measurement policy:

- **AOT compile first** (``jax.jit(...).lower(...).compile()``), so a
  trial never times tracing;
- **two disjoint timed windows** with a hard device sync inside each
  timed region; the point estimate is the combined rate and the
  window-to-window spread is reported per trial as its noise floor;
- timestamps come from THE project clock (``obs.trace.monotonic_s``) and
  every trial runs under a ``tune_trial`` span, so a search shows up in
  Perfetto as one track of compile+window spans per candidate (RUNBOOK
  "Autotuning schedules").

Error policy: a candidate that fails to compile or run is a FAILED TRIAL
(recorded with its error, never the winner) — a too-big tile must not
kill the search.  An op with no successful exact-semantics trial fails
the search.

Semantics policy (tune/candidates.py): ``pre_nms_size`` changes detection
semantics, so non-default values are measured only when the caller opts
in, every such trial is recorded with ``semantics: "approx"``, and the
WINNER is always chosen among exact-semantics trials — a human promotes
an approx trial to a winner deliberately, never the harness.

Pallas candidates only run on a TPU backend (Mosaic compiles them):
elsewhere they are recorded as skipped trials and the winner comes from
the XLA candidates — which is exactly what a CPU smoke run
(``make tune-smoke``) commits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.tune import candidates as cand_lib
from batchai_retinanet_horovod_coco_tpu.tune import schedule as schedule_lib

# The flagship bucket (benchmark/configs/retinanet-r50-fpn-800.json): the
# search defaults to measuring where the train/serve money is.
DEFAULT_HW = (800, 1344)
DEFAULT_BATCH = 8
DEFAULT_STEPS = 30  # per trial, split into two windows

@dataclasses.dataclass
class Trial:
    """One measured candidate (the artifact's ``trials`` records these)."""

    op: str
    params: dict[str, Any]
    ms_per_call: float | None
    window_ms: list[float]
    noise_pct: float | None
    semantics: str = "exact"
    status: str = "ok"  # "ok" | "failed" | "skipped"
    error: str | None = None

    def record(self) -> dict[str, Any]:
        return {
            "op": self.op,
            "params": self.params,
            "ms_per_call": self.ms_per_call,
            "window_ms": self.window_ms,
            "noise_pct": self.noise_pct,
            "semantics": self.semantics,
            "status": self.status,
            "error": self.error,
        }


def mosaic_available() -> bool:
    """Pallas TPU kernels need Mosaic — i.e. an actual TPU backend."""
    return jax.devices()[0].platform == "tpu"


def time_compiled(fn: Callable[[], Any], steps: int) -> tuple[float, list[float]]:
    """Two disjoint timed windows over an already-compiled nullary call;
    returns (ms_per_call, [window_ms, window_ms]).  Syncs inside each
    window (dispatch half the steps, one hard sync)."""
    half = max(1, steps // 2)
    window_ms: list[float] = []
    for _ in range(2):
        with trace.span("tune_window", steps=half):
            t0 = trace.monotonic_s()
            out = None
            for _ in range(half):
                out = fn()
            jax.block_until_ready(out)
            dt = trace.monotonic_s() - t0
        window_ms.append(dt / half * 1e3)
    return sum(window_ms) / len(window_ms), window_ms


def run_trial(
    op: str,
    params: dict[str, Any],
    build: Callable[[dict[str, Any]], Callable[[], Any]],
    steps: int,
    semantics: str = "exact",
) -> Trial:
    """Compile + warm + time one candidate; a failure becomes a failed
    trial carrying its error."""
    with trace.span("tune_trial", op=op, **{
        k: v for k, v in params.items() if isinstance(v, (int, str))
    }):
        try:
            with trace.span("tune_compile", op=op):
                fn = build(params)
                out = fn()  # warmup call 1 (first real dispatch)
                out = fn()  # warmup call 2 (autotune/cache settled)
                jax.block_until_ready(out)
            ms, window_ms = time_compiled(fn, steps)
        except Exception as e:  # noqa: BLE001 — recorded on the trial
            return Trial(
                op=op, params=params, ms_per_call=None, window_ms=[],
                noise_pct=None, semantics=semantics, status="failed",
                error=str(e)[-500:],
            )
    noise = (
        abs(window_ms[0] - window_ms[1]) / max(ms, 1e-9) * 100
        if len(window_ms) == 2
        else None
    )
    return Trial(
        op=op, params=params, ms_per_call=round(ms, 3),
        window_ms=[round(w, 3) for w in window_ms],
        noise_pct=round(noise, 2) if noise is not None else None,
        semantics=semantics,
    )


# ---------------------------------------------------------------------------
# Per-op trial programs (synthetic inputs)
# ---------------------------------------------------------------------------


def _postprocess_inputs(batch: int, hw: tuple[int, int]):
    """The NMS search's input field: a sparse score distribution
    (sigmoid(-4 ± 1) ≈ 2% foreground) over the flagship anchor grid."""
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import DetectConfig
    from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib

    cfg = DetectConfig()
    anchors = anchors_lib.anchors_for_image_shape(hw, cfg.anchor)
    rng = np.random.default_rng(1)
    cls = jnp.asarray(
        rng.normal(-4.0, 1.0, (batch, anchors.shape[0], 80)).astype(np.float32)
    )
    deltas = jnp.asarray(
        rng.normal(0.0, 0.3, (batch, anchors.shape[0], 4)).astype(np.float32)
    )
    return jnp.asarray(anchors), cls, deltas


def _nms_builder(
    batch: int, hw: tuple[int, int]
) -> Callable[[dict[str, Any]], Callable[[], Any]]:
    from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
        DetectConfig,
        nms_fn_for,
    )
    from batchai_retinanet_horovod_coco_tpu.ops import boxes as boxes_lib

    anchors_dev, cls, deltas = _postprocess_inputs(batch, hw)

    def build(params: dict[str, Any]) -> Callable[[], Any]:
        # Every schedule knob pinned explicitly: the trial must measure
        # THIS candidate, not whatever the registry currently holds.
        cfg = DetectConfig(
            pre_nms_size=int(params.get("pre_nms_size", 1000)),
            nms_impl=str(params["impl"]),
            nms_block_k=int(params.get("block_k", 256)),
        )
        nms = nms_fn_for(cfg)

        def post(cls_logits, box_deltas):
            scores = jax.nn.sigmoid(cls_logits)
            boxes = boxes_lib.decode_boxes(
                anchors_dev[None], box_deltas, cfg.codec
            )
            boxes = boxes_lib.clip_boxes(boxes, hw)
            return nms(boxes, scores)

        compiled = jax.jit(post).lower(cls, deltas).compile()
        return lambda: compiled(cls, deltas)

    return build


def _focal_builder(
    batch: int, hw: tuple[int, int]
) -> Callable[[dict[str, Any]], Callable[[], Any]]:
    from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
    from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib

    num_anchors = anchors_lib.anchors_for_image_shape(hw).shape[0]
    rng = np.random.default_rng(2)
    logits = jnp.asarray(
        rng.normal(-4.0, 1.0, (batch, num_anchors, 80)).astype(np.float32)
    )
    labels = jnp.asarray(
        rng.integers(0, 80, (batch, num_anchors)).astype(np.int32)
    )
    # ~1% positive, ~4% ignored — a realistic assignment mix.
    state = jnp.asarray(
        rng.choice(
            np.array([-1, 0, 1], np.int32),
            (batch, num_anchors),
            p=[0.04, 0.95, 0.01],
        )
    )

    def build(params: dict[str, Any]) -> Callable[[], Any]:
        config = losses_lib.LossConfig(
            pallas_focal=params["impl"] == "pallas",
            focal_fwd_tile_a=params.get("fwd_tile_a"),
            focal_bwd_tile_a=params.get("bwd_tile_a"),
        )

        def loss_and_grad(x):
            # fwd + bwd: the train step always pays both.
            return jax.value_and_grad(
                lambda lg: jnp.sum(
                    losses_lib.focal_loss_compact(lg, labels, state, config)
                )
            )(x)

        compiled = jax.jit(loss_and_grad).lower(logits).compile()
        return lambda: compiled(logits)

    return build


def _matching_builder(
    batch: int, hw: tuple[int, int], num_gt: int = 32
) -> Callable[[dict[str, Any]], Callable[[], Any]]:
    from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
    from batchai_retinanet_horovod_coco_tpu.ops import matching as matching_lib

    anchors = jnp.asarray(anchors_lib.anchors_for_image_shape(hw))
    rng = np.random.default_rng(3)
    x1 = rng.uniform(0, hw[1] * 0.8, (batch, num_gt, 1))
    y1 = rng.uniform(0, hw[0] * 0.8, (batch, num_gt, 1))
    wh = rng.uniform(16, 256, (batch, num_gt, 2))
    gt_boxes = jnp.asarray(
        np.concatenate([x1, y1, x1 + wh[..., :1], y1 + wh[..., 1:]], -1)
        .astype(np.float32)
    )
    gt_labels = jnp.asarray(
        rng.integers(0, 80, (batch, num_gt)).astype(np.int32)
    )
    gt_mask = jnp.asarray(
        np.arange(num_gt)[None, :] < rng.integers(1, num_gt, (batch, 1))
    )

    def build(params: dict[str, Any]) -> Callable[[], Any]:
        config = matching_lib.MatchingConfig(
            fused_pallas=params["impl"] == "pallas",
            pallas_tile_a=params.get("tile_a"),
        )

        def assign(boxes, labels, mask):
            return matching_lib.anchor_targets_compact_batched(
                anchors, boxes, labels, mask, config
            )

        compiled = jax.jit(assign).lower(gt_boxes, gt_labels, gt_mask).compile()
        return lambda: compiled(gt_boxes, gt_labels, gt_mask)

    return build


# ---------------------------------------------------------------------------
# Search drivers
# ---------------------------------------------------------------------------

_BUILDERS: dict[str, Callable[..., Callable]] = {
    "nms": _nms_builder,
    "focal": _focal_builder,
    "matching": _matching_builder,
}


def _runnable(params: dict[str, Any], have_mosaic: bool) -> bool:
    return params.get("impl") != "pallas" or have_mosaic


def search_op(
    op: str,
    batch: int = DEFAULT_BATCH,
    hw: tuple[int, int] = DEFAULT_HW,
    steps: int = DEFAULT_STEPS,
    include_semantic: bool = False,
    candidates: list[dict[str, Any]] | None = None,
) -> tuple[dict[str, Any], list[Trial]]:
    """Measure every candidate for ``op``; returns (winner_entry, trials).

    The winner entry is directly mergeable into the registry's
    ``entries[op]`` (the candidate dicts are constructed that way).  Only
    exact-semantics successful trials are eligible winners.
    """
    if candidates is None:
        candidates = cand_lib.candidates_for(
            op, **({"include_semantic": True} if op == "nms" and include_semantic else {})
        )
    have_mosaic = mosaic_available()
    builder = _BUILDERS[op](batch, hw)
    trials: list[Trial] = []
    with trace.span("tune_search", op=op, candidates=len(candidates)):
        for params in candidates:
            semantics = (
                "approx"
                if op == "nms" and params.get("pre_nms_size", 1000) != 1000
                else "exact"
            )
            if not _runnable(params, have_mosaic):
                trials.append(Trial(
                    op=op, params=params, ms_per_call=None, window_ms=[],
                    noise_pct=None, semantics=semantics, status="skipped",
                    error="pallas candidate skipped: no Mosaic (non-TPU backend)",
                ))
                continue
            trials.append(run_trial(op, params, builder, steps, semantics))
    eligible = [
        t for t in trials if t.status == "ok" and t.semantics == "exact"
    ]
    if not eligible:
        raise RuntimeError(
            f"search_op({op!r}): no successful exact-semantics trial "
            f"(statuses: {[t.status for t in trials]})"
        )
    winner = min(eligible, key=lambda t: t.ms_per_call)
    return dict(winner.params), trials


def search_batch(
    hw: tuple[int, int] = DEFAULT_HW,
    steps: int = DEFAULT_STEPS,
    sizes: tuple[int, ...] = cand_lib.BATCH_SIZES,
    nms_entry: dict[str, Any] | None = None,
) -> tuple[int, list[Trial]]:
    """Per-bucket batch-size axis: highest postprocess THROUGHPUT
    (imgs/s, not ms/batch) over the detect postprocess at each candidate
    batch.  ``nms_entry`` (the just-searched NMS winner, when given) pins
    the suppression backend so the batch axis measures the tuned kernel.

    NOTE: this measures the postprocess program only (no backbone); no
    benchmark cell runs eval yet, so a winner has no end-to-end check.
    """
    entry = {"impl": "xla", **(nms_entry or {})}
    trials: list[Trial] = []
    with trace.span("tune_search", op="batch", candidates=len(sizes)):
        for b in sizes:
            builder = _nms_builder(b, hw)
            t = run_trial("batch", {"batch": b, **entry}, builder, steps)
            trials.append(t)
    ok = [t for t in trials if t.status == "ok"]
    if not ok:
        raise RuntimeError("search_batch: every candidate failed")
    # imgs/s = batch / (ms/1e3): maximize throughput, not per-call latency.
    winner = max(ok, key=lambda t: t.params["batch"] / t.ms_per_call)
    return int(winner.params["batch"]), trials


def compose_schedule(
    device_kind: str,
    entries: dict[str, dict[str, Any]],
    trials: list[Trial],
) -> dict[str, Any]:
    """Winner entries + trial records → a schema-valid registry artifact
    (validated here, so a buggy search can never write a poisoned one)."""
    doc = {
        "format": schedule_lib.FORMAT,
        "device_kind": device_kind,
        "entries": entries,
        "trials": [t.record() for t in trials],
    }
    schedule_lib.validate_schedule(doc)
    return doc


def run_search(
    ops: tuple[str, ...] = ("nms", "focal", "matching"),
    batch: int = DEFAULT_BATCH,
    hw: tuple[int, int] = DEFAULT_HW,
    steps: int = DEFAULT_STEPS,
    include_semantic: bool = False,
    search_batches: bool = False,
    device_kind: str | None = None,
) -> dict[str, Any]:
    """The full search: every requested op, winners composed into one
    artifact document (NOT yet saved — the CLI owns persistence so a dry
    run can print without writing)."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    entries: dict[str, dict[str, Any]] = {}
    all_trials: list[Trial] = []
    nms_entry: dict[str, Any] | None = None
    for op in ops:
        winner, trials = search_op(
            op, batch=batch, hw=hw, steps=steps,
            include_semantic=include_semantic,
        )
        entries[op] = winner
        all_trials.extend(trials)
        if op == "nms":
            nms_entry = winner
    if search_batches:
        best, trials = search_batch(hw=hw, steps=steps, nms_entry=nms_entry)
        all_trials.extend(trials)
        bucket = f"{hw[0]}x{hw[1]}"
        entries["eval"] = {"batch": {bucket: best}}
        # Serve also exports batch 1 so a lone straggler request never
        # pays a full winner-wide pad (serve/engine.batch_size_for).
        entries["serve"] = {
            "batch_sizes": {bucket: sorted({1, best})}
        }
    return compose_schedule(device_kind, entries, all_trials)
