"""Fused anchor→gt assignment Pallas kernel.

The XLA lowering of ``ops.matching.anchor_targets_compact`` materializes the
pairwise geometry in HBM — profiled at the flagship bucket (B=8, A=201600,
G=100): an f32[8, 201600, 100, 2] corner max/min intermediate (~1.3 GB of
writes+reads), two (A, G) argmax reductions, and the one-hot
(A, G) @ (G, 5) lookup matmul — ~9.5 ms end to end.  This kernel streams
anchor tiles through VMEM and never materializes anything A×G-shaped
off-chip.

Layout is chosen for the VPU: anchors ride the 128-lane minor dim and the
G gt boxes ride sublanes, so the per-anchor max/argmax over gts are FAST
sublane reductions over a (G, TILE_A) tile, and the matched-row lookup is
one f32 MXU dot ``packed^T (8, G) @ onehot (G, TILE_A)`` (HIGHEST precision
— each one-hot column selects exactly one row, so the result is bit-exact
f32).  The per-gt best-anchor reduction (force-match rescue) is the only
cross-lane reduce, done once per tile into a (G, 8) running accumulator.

IoU semantics match ``ops.iou.pairwise_iou`` exactly (degenerate/padded
boxes → IoU 0); tie-breaking matches ``jnp.argmax`` (first maximum).
Thresholding, the ≤G-row force-match scatter, and box encoding stay in jnp
(ops/matching.py) — (A,)-shaped, cheap, shared with the reference path.
Validated against the jnp path in tests/unit/test_pallas_matching.py.

MEASURED (v5e-1, flagship bucket B=8, A=201600, G=100): 5.4 ms vs 11.8 ms
for the XLA lowering in isolation (2.2x); inside the full train step the
wall-clock gain is small (~0.4 ms — XLA overlaps most of the matching with
conv work) but the kernel removes the 1.3 GB A×G HBM intermediate, which
lowers peak-memory pressure at larger batches.  An earlier layout with
anchors on sublanes and G on lanes measured 15.6 ms — every per-anchor
reduction was a cross-lane op; the transpose is what makes this kernel win.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_A = 8192

# Row layout of the transposed per-anchor result (B, 8, A).
ROW_MAX_IOU = 5  # rows 0..3 = matched box, 4 = label, 5 = max IoU
# Column layout of the per-gt accumulator (B, G, 8).
GT_COL_IOU, GT_COL_ANCHOR = 0, 1


def _kernel(anchors_ref, gt_ref, packedT_ref, out_ref, gtbest_ref, *, num_anchors):
    t = pl.program_id(1)
    a = anchors_ref[...].astype(jnp.float32)  # (4, TILE_A)
    gt = gt_ref[0].astype(jnp.float32)  # (G, 6): x1 y1 x2 y2 mask area
    packed_t = packedT_ref[0].astype(jnp.float32)  # (8, G)
    tile_a = a.shape[1]
    num_gt = gt.shape[0]

    x1a, y1a, x2a, y2a = (a[i : i + 1, :] for i in range(4))  # (1, TILE_A)
    x1g, y1g, x2g, y2g = (gt[:, i : i + 1] for i in range(4))  # (G, 1)
    gt_valid = gt[:, 4:5] > 0.0  # (G, 1)
    area_g = gt[:, 5:6]  # (G, 1)

    # IoU — same arithmetic as ops.iou.pairwise_iou.  (G, TILE_A)
    iw = jnp.maximum(jnp.minimum(x2a, x2g) - jnp.maximum(x1a, x1g), 0.0)
    ih = jnp.maximum(jnp.minimum(y2a, y2g) - jnp.maximum(y1a, y1g), 0.0)
    inter = iw * ih
    area_a = jnp.maximum(x2a - x1a, 0.0) * jnp.maximum(y2a - y1a, 0.0)
    union = area_a + area_g - inter
    iou = jnp.where(union > 0.0, inter / jnp.maximum(union, 1e-12), 0.0)
    iou = jnp.where(gt_valid, iou, 0.0)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile_a), 1)
    in_range = (t * tile_a + lane) < num_anchors  # (1, TILE_A)
    # Out-of-range anchors must not win the per-gt argmax below.
    iou = jnp.where(in_range, iou, -1.0)

    # Per-anchor max + first-argmax over gts: sublane reductions.
    max_iou = jnp.max(iou, axis=0, keepdims=True)  # (1, TILE_A)
    grow = jax.lax.broadcasted_iota(jnp.int32, iou.shape, 0)
    first = jnp.min(
        jnp.where(iou == max_iou, grow, num_gt), axis=0, keepdims=True
    )  # (1, TILE_A)
    onehot = (grow == first).astype(jnp.float32)  # (G, TILE_A)
    sel = jax.lax.dot_general(
        packed_t,
        onehot,
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )  # (8, TILE_A)
    row8 = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 0)
    out_ref[0] = sel + jnp.where(row8 == ROW_MAX_IOU, max_iou, 0.0)

    # Per-gt running best across anchor tiles (first-tie like jnp.argmax:
    # strict > keeps the earlier tile; min-of-lanes breaks ties within one).
    tile_best = jnp.max(iou, axis=1, keepdims=True)  # (G, 1)
    lane_global = (t * tile_a + lane).astype(jnp.int32)
    tile_arg = jnp.min(
        jnp.where(iou == tile_best, lane_global, num_anchors),
        axis=1,
        keepdims=True,
    ).astype(jnp.float32)
    gcol = jax.lax.broadcasted_iota(jnp.int32, (num_gt, 8), 1)
    update = (
        tile_best * (gcol == GT_COL_IOU) + tile_arg * (gcol == GT_COL_ANCHOR)
    )

    @pl.when(t == 0)
    def _():
        gtbest_ref[0] = update

    @pl.when(t > 0)
    def _():
        cur = gtbest_ref[0]  # (G, 8)
        better = cur[:, GT_COL_IOU : GT_COL_IOU + 1] < tile_best  # (G, 1)
        gtbest_ref[0] = jnp.where(better, update, cur)


@functools.partial(
    jax.jit, static_argnames=("interpret", "planar", "tile_a")
)
def assign_fused(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_labels: jnp.ndarray,
    gt_mask: jnp.ndarray,
    interpret: bool = False,
    planar: bool = False,
    tile_a: int | None = None,
):
    """Batched fused assignment.

    Args:
      anchors: (A, 4) f32 corner boxes (shared across the batch).
      gt_boxes: (B, G, 4) padded corner boxes.
      gt_labels: (B, G) int32.
      gt_mask: (B, G) bool.
      planar: return matched boxes coordinate-planar (B, 4, A) — a FREE
        slice of the kernel's transposed output, where the default (B, A, 4)
        form costs a moveaxis copy of a 32x-lane-padded tensor (~206 MB of
        tiles at the flagship bucket; see ops.boxes.encode_boxes_planar).
      tile_a: anchor-tile width (None = module default TILE_A); must be a
        positive multiple of 128.

    Returns:
      matched_boxes (B, A, 4) f32 — or (B, 4, A) when ``planar`` —
      matched_labels (B, A) int32, max_iou (B, A) f32, gt_best_iou (B, G)
      f32, gt_best_anchor (B, G) int32.
    """
    tile = TILE_A if tile_a is None else int(tile_a)
    batch, num_gt, _ = gt_boxes.shape
    num_anchors = anchors.shape[0]
    boxes = gt_boxes.astype(jnp.float32)
    w = jnp.maximum(boxes[..., 2] - boxes[..., 0], 0.0)
    h = jnp.maximum(boxes[..., 3] - boxes[..., 1], 0.0)
    gt = jnp.concatenate(
        [
            boxes,
            gt_mask[..., None].astype(jnp.float32),
            (w * h)[..., None],
        ],
        axis=-1,
    )  # (B, G, 6)
    packed_t = jnp.stack(
        [
            boxes[..., 0], boxes[..., 1], boxes[..., 2], boxes[..., 3],
            gt_labels.astype(jnp.float32),
            jnp.zeros((batch, num_gt), jnp.float32),
            jnp.zeros((batch, num_gt), jnp.float32),
            jnp.zeros((batch, num_gt), jnp.float32),
        ],
        axis=1,
    )  # (B, 8, G)

    grid = (batch, pl.cdiv(num_anchors, tile))
    out, gtbest = pl.pallas_call(
        functools.partial(_kernel, num_anchors=num_anchors),
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, tile), lambda b, t: (0, t),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, num_gt, 6), lambda b, t: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, num_gt), lambda b, t: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 8, tile), lambda b, t: (b, 0, t),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, num_gt, 8), lambda b, t: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, 8, num_anchors), jnp.float32),
            jax.ShapeDtypeStruct((batch, num_gt, 8), jnp.float32),
        ],
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        # The Mosaic call's HLO instruction is named after this, not after
        # whichever jit holds it; trace readers match "assign_fused".
        name="assign_fused",
    )(jnp.moveaxis(anchors.astype(jnp.float32), 0, 1), gt, packed_t)

    matched_boxes = (
        out[:, :4, :] if planar else jnp.moveaxis(out[:, :4, :], 1, 2)
    )
    matched_labels = out[:, 4, :].astype(jnp.int32)
    max_iou = out[:, ROW_MAX_IOU, :]
    gt_best_iou = gtbest[..., GT_COL_IOU]
    gt_best_anchor = gtbest[..., GT_COL_ANCHOR].astype(jnp.int32)
    return matched_boxes, matched_labels, max_iou, gt_best_iou, gt_best_anchor
