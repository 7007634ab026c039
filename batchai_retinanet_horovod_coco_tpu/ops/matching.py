"""IoU-based anchor→gt target assignment as a jit'd device op.

Capability parity with keras-retinanet's ``anchor_targets_bbox`` /
``compute_gt_annotations`` (SURVEY.md M5): per-anchor argmax-IoU assignment
with IoU ≥ 0.5 positive, < 0.4 negative, in-between ignored — but executed on
device, vmapped over the batch, instead of per-image on the host loader thread
(SURVEY.md call stack 3.3).

Design notes (TPU-first):
- GT boxes arrive padded to a fixed ``max_gt`` with a validity mask, keeping
  every shape static.  Padded rows are degenerate boxes → IoU 0 → can never
  become positives; we additionally mask them explicitly for robustness.
- In addition to the per-anchor rule we force-assign, for every valid gt, the
  anchor with the highest IoU (the RetinaNet paper's low-quality-match rescue;
  without it small objects can end up with zero positive anchors).
- Outputs are dense fixed-shape tensors consumed directly by the losses.
  The train step uses the compact form (:func:`anchor_targets_compact`):
  integer matched labels, box-delta targets, and a per-anchor state in
  {-1 ignore, 0 negative, 1 positive}; the focal loss reconstructs the
  one-hot implicitly.  :func:`anchor_targets` materializes the one-hot
  (A, K) form for tests/tools (the keras-retinanet surface).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.ops.boxes import (
    BoxCodecConfig,
    encode_boxes,
    encode_boxes_planar,
)
from batchai_retinanet_horovod_coco_tpu.ops.iou import pairwise_iou

IGNORE = -1
NEGATIVE = 0
POSITIVE = 1


@dataclasses.dataclass(frozen=True)
class MatchingConfig:
    positive_iou: float = 0.5
    negative_iou: float = 0.4
    # Force-match each gt's best anchor even below positive_iou.
    force_match_best: bool = True
    # Batched assignment via the fused Pallas kernel (ops/pallas/matching.py)
    # instead of the vmapped XLA lowering: None = auto (TPU backend only),
    # True/False = force.  See the kernel module docstring for the measured
    # HBM-traffic win.
    fused_pallas: bool | None = None
    # Interpreter-mode pallas (CPU tests of the fused path).
    pallas_interpret: bool = False
    # Anchor-tile width for the fused kernel: the value of
    # ops/pallas/matching.TILE_A (not imported: this module stays free of
    # Pallas; tests/unit/test_kernel_constants.py holds the two equal).
    pallas_tile_a: int = 8192


class AnchorAssignment(NamedTuple):
    matched_gt: jnp.ndarray  # (A,) int32 index into gt rows (0 if unmatched)
    state: jnp.ndarray  # (A,) int32 in {IGNORE, NEGATIVE, POSITIVE}


class AnchorTargets(NamedTuple):
    cls_targets: jnp.ndarray  # (A, num_classes) one-hot float
    box_targets: jnp.ndarray  # (A, 4) encoded deltas (valid where positive)
    state: jnp.ndarray  # (A,) int32


class CompactTargets(NamedTuple):
    """Targets without the dense (A, K) one-hot — the train-step form.

    The one-hot classification target is recoverable as
    ``(matched_labels[:, None] == arange(K)) & (state == POSITIVE)``; keeping
    it implicit lets the focal loss fuse that comparison into its elementwise
    computation instead of writing a (B, A, K) float tensor to HBM (~0.5 GB
    per step at the flagship bucket — measured 49 ms → see losses.py).
    """

    matched_labels: jnp.ndarray  # (A,) int32 class id of the matched gt
    box_targets: jnp.ndarray  # (A, 4) encoded deltas (valid where positive)
    state: jnp.ndarray  # (A,) int32


def _finalize_states(
    max_iou: jnp.ndarray,
    gt_best_iou: jnp.ndarray,
    gt_best_anchor: jnp.ndarray,
    gt_mask: jnp.ndarray,
    num_anchors: int,
    config: MatchingConfig,
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """The matching RULE, shared by the XLA and fused-Pallas paths.

    Thresholds + force-match rescue from the per-anchor/per-gt IoU
    reductions (one image).  Returns ``(state, forced_target)`` where
    ``forced_target`` (G,) routes each force-matched gt to its best anchor
    (out-of-range index ``num_anchors`` = not forced, dropped by scatters);
    None when force-matching is disabled.

    Keeping this in ONE place is what guarantees the two assignment
    backends can never drift apart on the rule itself (the kernels only
    compute reductions; tests/unit/test_pallas_matching.py pins equality).
    """
    any_gt = jnp.any(gt_mask)
    positive = (max_iou >= config.positive_iou) & any_gt
    negative = max_iou < config.negative_iou

    forced_target = None
    if config.force_match_best:
        # For each valid gt with some overlap (> 0), its argmax anchor
        # becomes positive for that gt.  Non-forced gts (padding / no
        # overlap) are routed to out-of-range index A so mode="drop"
        # discards them — they must not clobber real writes at anchor 0
        # (argmax of an all-zero IoU column is 0).
        force = gt_mask & (gt_best_iou > 0.0)
        forced_target = jnp.where(force, gt_best_anchor, num_anchors)
        forced_flag = jnp.zeros(num_anchors, dtype=bool).at[forced_target].set(
            True, mode="drop"
        )
        positive = positive | forced_flag
        negative = negative & ~forced_flag

    state = jnp.full(num_anchors, IGNORE, dtype=jnp.int32)
    state = jnp.where(negative, NEGATIVE, state)
    state = jnp.where(positive, POSITIVE, state)
    return state, forced_target


def assign_anchors(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_mask: jnp.ndarray,
    config: MatchingConfig = MatchingConfig(),
) -> AnchorAssignment:
    """Assign each of A anchors to one of G (padded) gt boxes.

    Args:
      anchors: (A, 4) corner boxes.
      gt_boxes: (G, 4) corner boxes, padded rows arbitrary.
      gt_mask: (G,) bool, True for real gt rows.
    """
    num_anchors = anchors.shape[0]
    iou = pairwise_iou(anchors, gt_boxes)  # (A, G)
    iou = jnp.where(gt_mask[None, :], iou, 0.0)

    matched_gt = jnp.argmax(iou, axis=1).astype(jnp.int32)  # (A,)
    max_iou = jnp.max(iou, axis=1)  # (A,)

    state, forced_target = _finalize_states(
        max_iou,
        jnp.max(iou, axis=0),
        jnp.argmax(iou, axis=0).astype(jnp.int32),
        gt_mask,
        num_anchors,
        config,
    )
    if forced_target is not None:
        forced_flag = jnp.zeros(num_anchors, dtype=bool).at[forced_target].set(
            True, mode="drop"
        )
        forced_idx = (
            jnp.zeros(num_anchors, dtype=jnp.int32)
            .at[forced_target]
            .set(jnp.arange(gt_boxes.shape[0], dtype=jnp.int32), mode="drop")
        )
        matched_gt = jnp.where(forced_flag, forced_idx, matched_gt)
    return AnchorAssignment(matched_gt=matched_gt, state=state)


def anchor_targets_compact(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_labels: jnp.ndarray,
    gt_mask: jnp.ndarray,
    matching: MatchingConfig = MatchingConfig(),
    codec: BoxCodecConfig = BoxCodecConfig(),
) -> CompactTargets:
    """Per-anchor targets for one image, classification kept as int labels.

    vmap over a leading batch axis for batched use (anchors held constant):
    ``jax.vmap(anchor_targets_compact, in_axes=(None, 0, 0, 0))``.
    """
    assignment = assign_anchors(anchors, gt_boxes, gt_mask, matching)
    # Matched gt rows via one-hot matmul rather than a gather: a TPU gather of
    # ~200k rows from a tiny table serializes (profiled at ~20 ms/step at the
    # flagship bucket, the single hottest op) while the (A, G) @ (G, 5) dot is
    # MXU work measured at ~2 ms.  HIGHEST precision keeps it bit-exact in
    # f32 (each one-hot row selects exactly one value; default TPU matmul
    # precision would round coords through bf16).
    num_gt = gt_boxes.shape[0]
    onehot = (
        assignment.matched_gt[:, None] == jnp.arange(num_gt, dtype=jnp.int32)
    ).astype(jnp.float32)  # (A, G)
    packed = jnp.concatenate(
        [gt_boxes.astype(jnp.float32), gt_labels.astype(jnp.float32)[:, None]],
        axis=1,
    )  # (G, 5): x1 y1 x2 y2 label
    matched = jnp.dot(onehot, packed, precision=jax.lax.Precision.HIGHEST)
    matched_boxes = matched[:, :4]  # (A, 4)
    matched_labels = matched[:, 4].astype(jnp.int32)  # (A,)

    positive = assignment.state == POSITIVE
    box_targets = encode_boxes(anchors, matched_boxes, codec)
    box_targets = jnp.where(positive[:, None], box_targets, 0.0)
    return CompactTargets(
        matched_labels=matched_labels,
        box_targets=box_targets,
        state=assignment.state,
    )


def anchor_targets_compact_batched(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_labels: jnp.ndarray,
    gt_mask: jnp.ndarray,
    matching: MatchingConfig = MatchingConfig(),
    codec: BoxCodecConfig = BoxCodecConfig(),
    planar_box_targets: bool = False,
) -> CompactTargets:
    """Batched :func:`anchor_targets_compact` — the train-step entrypoint.

    Dispatches between the vmapped XLA path and the fused Pallas kernel
    (``MatchingConfig.fused_pallas``); both produce identical targets
    (tests/unit/test_pallas_matching.py).  Inputs carry a leading batch dim
    except ``anchors`` (shared).

    ``planar_box_targets``: return ``box_targets`` coordinate-planar as
    (B, 4, A) instead of (B, A, 4).  On TPU a 4-minor f32 tensor tiles at
    ~3% lane occupancy (206 MB of T(8,128) tiles at the flagship bucket),
    and every op touching it — the kernel-output moveaxis, the encode, the
    positive mask, the per-level loss retile — pays that tax; the planar
    form is the same values in a dense layout (identical per-element
    arithmetic, see ops.boxes.encode_boxes_planar).  The train step's NHWC
    loss path consumes this form.
    """
    fused = matching.fused_pallas
    if fused is None:
        fused = jax.default_backend() == "tpu"
    if not fused:
        targets = jax.vmap(
            anchor_targets_compact, in_axes=(None, 0, 0, 0, None, None)
        )(anchors, gt_boxes, gt_labels, gt_mask, matching, codec)
        if planar_box_targets:
            targets = targets._replace(
                box_targets=jnp.moveaxis(targets.box_targets, -1, -2)
            )
        return targets

    from batchai_retinanet_horovod_coco_tpu.ops.pallas.matching import (
        assign_fused,
    )

    matched_boxes, matched_labels, max_iou, gt_best_iou, gt_best_anchor = (
        assign_fused(
            anchors, gt_boxes, gt_labels, gt_mask,
            interpret=matching.pallas_interpret,
            planar=planar_box_targets,
            tile_a=matching.pallas_tile_a,
        )
    )
    num_anchors = anchors.shape[0]

    def finish_one(miou, best_iou, best_anchor, boxes, labels, mask, mb, ml):
        state, forced_target = _finalize_states(
            miou, best_iou, best_anchor, mask, num_anchors, matching
        )
        if forced_target is not None:
            # The kernel's matched rows reflect the pre-force argmax; patch
            # the ≤G force-matched anchors with their gt's box/label.
            if planar_box_targets:
                # mb is (4, A): scatter the gt coords along lanes.
                mb = mb.at[:, forced_target].set(
                    jnp.moveaxis(boxes.astype(jnp.float32), 0, 1), mode="drop"
                )
            else:
                mb = mb.at[forced_target].set(
                    boxes.astype(jnp.float32), mode="drop"
                )
            ml = ml.at[forced_target].set(
                labels.astype(jnp.int32), mode="drop"
            )
        return state, mb, ml

    state, matched_boxes, matched_labels = jax.vmap(finish_one)(
        max_iou, gt_best_iou, gt_best_anchor, gt_boxes, gt_labels, gt_mask,
        matched_boxes, matched_labels,
    )

    positive = state == POSITIVE
    if planar_box_targets:
        box_targets = encode_boxes_planar(
            jnp.moveaxis(anchors, 0, 1)[None], matched_boxes, codec
        )
        box_targets = jnp.where(positive[..., None, :], box_targets, 0.0)
    else:
        box_targets = encode_boxes(anchors[None], matched_boxes, codec)
        box_targets = jnp.where(positive[..., None], box_targets, 0.0)
    return CompactTargets(
        matched_labels=matched_labels,
        box_targets=box_targets,
        state=state,
    )


def anchor_targets(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_labels: jnp.ndarray,
    gt_mask: jnp.ndarray,
    num_classes: int,
    matching: MatchingConfig = MatchingConfig(),
    codec: BoxCodecConfig = BoxCodecConfig(),
) -> AnchorTargets:
    """Dense per-anchor classification + regression targets for one image.

    The keras-retinanet ``anchor_targets_bbox`` surface (one-hot cls targets).
    The train step uses :func:`anchor_targets_compact` instead — materializing
    (A, K) here is fine for tests/tools but wasteful inside the hot step.
    The one-hot is built with a broadcast compare, not a scatter: TPU scatter
    serializes; an (A, K) equality against an iota vectorizes on the VPU.
    """
    compact = anchor_targets_compact(
        anchors, gt_boxes, gt_labels, gt_mask, matching, codec
    )
    positive = compact.state == POSITIVE
    cls_targets = jnp.where(
        positive[:, None]
        & (
            compact.matched_labels[:, None]
            == jnp.arange(num_classes, dtype=jnp.int32)[None, :]
        ),
        1.0,
        0.0,
    ).astype(jnp.float32)
    return AnchorTargets(
        cls_targets=cls_targets,
        box_targets=compact.box_targets,
        state=compact.state,
    )
