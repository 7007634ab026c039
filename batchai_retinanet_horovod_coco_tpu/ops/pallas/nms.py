"""Fused blocked-suppression NMS Pallas kernel (ISSUE 6) — opt-in.

NMS is the stage of the detect postprocess with the rewrite history
(ops/nms.py).  The XLA lowering of :func:`ops.nms.greedy_keep` materializes
the full (K, K) IoU matrix in one shot and re-reduces ALL of it on every
fixed-point iteration — at K=1000 that is ~4 MB re-read per iteration per
image, and the iteration count is the worst suppression-chain depth.

This kernel computes the SAME exact greedy solution block-sequentially:
candidates (already sorted by descending score) are processed in
``block_k``-wide blocks; block i first ORs in suppression from the
already-final kept set of blocks j < i (one masked (BK, BK) IoU tile
reduce per earlier block — each tile is computed once, in VMEM, and never
written to HBM), then resolves its intra-block ordering with the
fixed-point iteration on a single (BK, BK) tile, whose chain depth is
bounded by the block instead of the whole candidate set.  Score
threshold + top-K candidate selection and the fixed-width compaction are
the shared jnp stages (``ops.nms.select_candidates`` /
``compact_keep`` / ``build_detections``) — the kernel replaces only the
suppression stage, so the two backends cannot drift on selection
semantics.

Same-class masking matches ops/nms.py exactly (cross-class IoU zeroed
BEFORE thresholding); the IoU arithmetic replicates
``ops.iou.pairwise_iou`` op-for-op, so keep decisions are bit-identical —
pinned through the full detect path by tests/unit/test_pallas_nms.py.

Layout: candidates ride the 128-lane minor dim (columns of each IoU
tile); the suppressing rows come from a row-major (K, 4) copy of the
same boxes so no in-kernel transposes of box data are needed.  The one
lane→sublane transpose per fixed-point iteration (the kept mask feeding
the next reduce) is done with an exact 0/1 identity matmul
(``Precision.HIGHEST``) — Mosaic has no cheap vector transpose, and the
dot is exact on {0, 1} values.

``block_k`` defaults to ``DEFAULT_BLOCK_K`` (``DetectConfig.nms_block_k``
states the same value).  The jnp reference (``use_kernel=False``) is the
vmapped ``ops.nms.greedy_keep``.

Compiled by Mosaic (libtpu 0.0.34) and bit-identical to the XLA path on a
v5e at B=8, K=1000, ``block_k`` 128, 256 and 512: chip_smoke.py's
``kernels`` phase repeats that on every run.  The rows and class ids of
a block are read through the refs (``ref[0, pl.ds(start, block_k), :]``):
Mosaic has no ``dynamic_slice`` of a loaded value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from batchai_retinanet_horovod_coco_tpu.ops import nms as nms_lib

# Default (K, K) IoU tile width: 256 keeps every live tile (iou, masks,
# eye) under ~1 MB of VMEM while amortizing the per-block fixed point.
# Untimed on the chip against 128 and 512 (ROADMAP S6).
DEFAULT_BLOCK_K = 256


def _tile_iou(rows: jnp.ndarray, cols_t: jnp.ndarray) -> jnp.ndarray:
    """(BK_rows, BK_cols) IoU tile from row-major rows (BK, 4) and planar
    columns (4, BK) — the SAME elementwise arithmetic as
    ``ops.iou.pairwise_iou`` (max/min corners → clamped wh → product →
    union guard), so results are bit-identical to the jnp path."""
    x1r, y1r = rows[:, 0:1], rows[:, 1:2]  # (BK, 1)
    x2r, y2r = rows[:, 2:3], rows[:, 3:4]
    x1c, y1c = cols_t[0:1, :], cols_t[1:2, :]  # (1, BK)
    x2c, y2c = cols_t[2:3, :], cols_t[3:4, :]
    iw = jnp.maximum(jnp.minimum(x2r, x2c) - jnp.maximum(x1r, x1c), 0.0)
    ih = jnp.maximum(jnp.minimum(y2r, y2c) - jnp.maximum(y1r, y1c), 0.0)
    inter = iw * ih
    area_r = jnp.maximum(x2r - x1r, 0.0) * jnp.maximum(y2r - y1r, 0.0)
    area_c = jnp.maximum(x2c - x1c, 0.0) * jnp.maximum(y2c - y1c, 0.0)
    union = area_r + area_c - inter
    return jnp.where(union > 0.0, inter / jnp.maximum(union, 1e-12), 0.0)


def _kernel(
    rows_ref,  # (1, K_pad, 4) f32 row-major boxes
    cols_ref,  # (1, 4, BK) f32 planar boxes, block i's columns
    clsr_ref,  # (1, K_pad, 1) i32 class ids, row orientation
    clsc_ref,  # (1, 1, BK) i32 class ids, block i's columns
    valid_ref,  # (1, 1, BK) f32 0/1 validity, block i's columns
    out_ref,  # (1, 1, BK) f32 0/1 keep mask, block i
    keep_ref,  # scratch (K_pad, 1) f32 — final keep, sublane orientation
    *,
    block_k: int,
    iou_threshold: float,
):
    i = pl.program_id(1)
    cols_t = cols_ref[0]  # (4, BK)
    cls_c = clsc_ref[0]  # (1, BK)
    valid_c = valid_ref[0]  # (1, BK)

    def masked_tile(rows, cls_r):
        # Cross-class IoU zeroed BEFORE thresholding — ops/nms.py order.
        iou = _tile_iou(rows, cols_t)
        return jnp.where(cls_r == cls_c, iou, 0.0)

    # Suppression from the already-final kept set of blocks j < i: all
    # their candidates precede (outscore) every column of block i, so no
    # triangular mask is needed — kept ∧ IoU > t suffices.
    def prev_block(j, supp):
        rj = pl.multiple_of(j * block_k, block_k)
        rows = rows_ref[0, pl.ds(rj, block_k), :]  # (BK, 4)
        cls_r = clsr_ref[0, pl.ds(rj, block_k), :]  # (BK, 1)
        kept = keep_ref[pl.ds(rj, block_k), :]  # (BK, 1)
        hit = (masked_tile(rows, cls_r) > iou_threshold) & (kept > 0.5)
        return jnp.maximum(
            supp, jnp.max(jnp.where(hit, 1.0, 0.0), axis=0, keepdims=True)
        )

    supp_prev = lax.fori_loop(
        0, i, prev_block, jnp.zeros((1, block_k), jnp.float32)
    )

    # Intra-block greedy fixed point on one (BK, BK) tile (the
    # ops.nms.greedy_keep iteration, block-local: chain depth ≤ block_k).
    ci = pl.multiple_of(i * block_k, block_k)
    rows_i = rows_ref[0, pl.ds(ci, block_k), :]
    cls_ri = clsr_ref[0, pl.ds(ci, block_k), :]
    rr = lax.broadcasted_iota(jnp.int32, (block_k, block_k), 0)
    cc = lax.broadcasted_iota(jnp.int32, (block_k, block_k), 1)
    sup_ii = (masked_tile(rows_i, cls_ri) > iou_threshold) & (rr < cc)
    eye = (rr == cc).astype(jnp.float32)
    valid_i = valid_c * (1.0 - supp_prev)  # (1, BK) 0/1

    def to_sub(lanes):
        # Exact lane→sublane transpose of a 0/1 mask via identity matmul:
        # (BK, BK) @ (BK, 1); exact in f32 on {0, 1} at HIGHEST precision.
        return lax.dot_general(
            eye, lanes, (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
        )

    def cond(carry):
        keep, prev, it = carry
        return jnp.any(keep != prev) & (it < block_k)

    def body(carry):
        keep, _, it = carry
        hit = sup_ii & (to_sub(keep) > 0.5)
        suppressed = jnp.max(jnp.where(hit, 1.0, 0.0), axis=0, keepdims=True)
        return valid_i * (1.0 - suppressed), keep, it + 1

    keep_i, _, _ = lax.while_loop(
        cond, body, (valid_i, jnp.zeros_like(valid_i), jnp.int32(0))
    )
    keep_ref[pl.ds(ci, block_k), :] = to_sub(keep_i)
    out_ref[0] = keep_i


def nms_keep_mask(
    cand_boxes: jnp.ndarray,
    cand_scores: jnp.ndarray,
    class_idx: jnp.ndarray,
    iou_threshold: float = 0.5,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched (B, K) greedy keep mask over SORTED candidates, via the
    blocked Pallas kernel.  Inputs must be in descending-score order per
    image (``select_candidates`` output); entries with score ≤ _NEG_INF/2
    are padding.  Bit-identical to ``vmap(ops.nms.greedy_keep)``.
    """
    if block_k % 128 != 0:
        raise ValueError(f"block_k must be a multiple of 128, got {block_k}")
    batch, k = cand_scores.shape
    k_pad = -(-k // block_k) * block_k
    pad = k_pad - k
    boxes = jnp.pad(
        cand_boxes.astype(jnp.float32), ((0, 0), (0, pad), (0, 0))
    )
    scores = jnp.pad(
        cand_scores.astype(jnp.float32),
        ((0, 0), (0, pad)),
        constant_values=nms_lib._NEG_INF,
    )
    cls = jnp.pad(
        class_idx.astype(jnp.int32), ((0, 0), (0, pad)), constant_values=-1
    )
    valid = (scores > nms_lib._NEG_INF / 2).astype(jnp.float32)

    grid = (batch, k_pad // block_k)
    keep = pl.pallas_call(
        functools.partial(
            _kernel, block_k=block_k, iou_threshold=float(iou_threshold)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, k_pad, 4), lambda b, i: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 4, block_k), lambda b, i: (b, 0, i),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, k_pad, 1), lambda b, i: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k), lambda b, i: (b, 0, i),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k), lambda b, i: (b, 0, i),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_k), lambda b, i: (b, 0, i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((batch, 1, k_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k_pad, 1), jnp.float32)],
        # The block dim is "arbitrary": block i reads the kept state every
        # j < i wrote (keep_ref scratch persists across grid steps within
        # one core; rows are always written before read within a batch).
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        boxes,
        jnp.moveaxis(boxes, 1, 2),
        cls[..., None],
        cls[:, None, :],
        valid[:, None, :],
    )
    return keep[:, 0, :k] > 0.5


def nms_keep_mask_reference(
    cand_boxes: jnp.ndarray,
    cand_scores: jnp.ndarray,
    class_idx: jnp.ndarray,
    iou_threshold: float = 0.5,
) -> jnp.ndarray:
    """Pure-jnp fallback of :func:`nms_keep_mask`: the vmapped exact
    fixed point the XLA path uses (``ops.nms.greedy_keep``)."""
    return jax.vmap(
        lambda b, s, c: nms_lib.greedy_keep(b, s, iou_threshold, c)
    )(cand_boxes, cand_scores, class_idx)


def batched_multiclass_nms_pallas(
    boxes: jnp.ndarray,
    cls_scores: jnp.ndarray,
    score_threshold: float = 0.05,
    iou_threshold: float = 0.5,
    pre_nms_size: int = 1000,
    max_detections: int = 300,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    use_kernel: bool = True,
) -> nms_lib.Detections:
    """Drop-in for ``ops.nms.batched_multiclass_nms`` with the suppression
    stage on the blocked Pallas kernel (``use_kernel=False`` = the pure-jnp
    fallback, same shared stages, for backends without Mosaic).

    Candidate selection and compaction are the shared jnp stages, so the
    output is bit-identical to the XLA path (tests/unit/test_pallas_nms.py
    pins this through ``collect_detections``).
    """
    sel_one = functools.partial(
        nms_lib.select_candidates,
        score_threshold=score_threshold,
        pre_nms_size=pre_nms_size,
    )
    cand_boxes, cand_scores, class_idx = jax.vmap(sel_one)(boxes, cls_scores)
    if use_kernel:
        keep = nms_keep_mask(
            cand_boxes, cand_scores, class_idx, iou_threshold,
            block_k=block_k, interpret=interpret,
        )
    else:
        keep = nms_keep_mask_reference(
            cand_boxes, cand_scores, class_idx, iou_threshold
        )

    def tail(cb, sc, cl, kp):
        sel, valid = nms_lib.compact_keep(sc, kp, max_detections)
        return nms_lib.build_detections(cb, sc, cl, sel, valid)

    return jax.vmap(tail)(cand_boxes, cand_scores, class_idx, keep)
