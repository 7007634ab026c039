"""Detection losses: focal classification loss + smooth-L1 box regression.

Capability parity with keras-retinanet ``losses.py`` (SURVEY.md M4):
- focal loss with alpha=0.25, gamma=2.0, computed on sigmoid logits over all
  non-ignored anchors;
- smooth-L1 with sigma=3 (beta = 1/sigma^2) on positive anchors only.

Normalization — DELIBERATE divergence from keras-retinanet: the reference
divides the batch-wide loss sum by the batch-wide positive count; we
normalize by the PER-IMAGE positive count (min 1) and then average over the
batch.  This (a) matches the RetinaNet paper's definition ("the total focal
loss of an image, normalized by the number of anchors assigned to
ground-truth boxes"), and (b) is exactly invariant under data-parallel
sharding: mean-over-images equals pmean of per-shard means regardless of how
positives distribute across shards, so the sharded step is bitwise-comparable
to the single-device step (tests/distributed/test_train_step.py).  The
reference's batch-global normalizer is NOT DP-invariant.

TPU-first differences from the reference:
- Losses consume the fixed-shape targets produced on device by
  ``ops.matching`` (the reference computed targets on the host loader thread
  and shipped them with the batch).  The train step uses the compact
  integer-label form (``total_loss_compact``/``focal_loss_compact``) so the
  (A, K) one-hot never hits HBM; the dense ``total_loss`` surface remains for
  tests/tools.
- Everything is expressed on logits (numerically stable
  log-sigmoid formulation), in the computation dtype of the model (bf16-safe:
  reductions accumulate in f32).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import nn

from batchai_retinanet_horovod_coco_tpu.ops import matching


def _normalize_per_image(
    per_image: jnp.ndarray, anchor_state: jnp.ndarray
) -> jnp.ndarray:
    """Mean over images of per_image / max(#positive anchors, 1).

    The DP-invariant normalization described in the module docstring — the
    single definition shared by every loss path.
    """
    num_pos = jnp.sum(
        (anchor_state == matching.POSITIVE).astype(jnp.float32), axis=-1
    )
    return jnp.mean(per_image / jnp.maximum(num_pos, 1.0))


@dataclasses.dataclass(frozen=True)
class LossConfig:
    focal_alpha: float = 0.25
    focal_gamma: float = 2.0
    smooth_l1_beta: float = 1.0 / 9.0  # sigma=3 in the reference parametrization
    box_loss_weight: float = 1.0
    # Fused Pallas focal kernel (ops/pallas/focal.py).  The hand kernel
    # measured ~2.8x SLOWER than XLA's lowering of the exp-form jnp path
    # at K=80 on v5e (3.6 vs 7.9 ms fwd; the K=80 minor dim wastes 37% of
    # the 128-lane VPU tiles), so only an explicit True turns it on.  It
    # stays bit-validated for K>=128 workloads.
    pallas_focal: bool = False
    # Run the Pallas kernel in interpreter mode (CPU tests of the wiring).
    pallas_interpret: bool = False
    # Anchor-tile widths for the fused kernel: the values of
    # ops/pallas/focal.FWD_TILE_A / BWD_TILE_A (not imported: this module
    # stays free of Pallas; tests/unit/test_kernel_constants.py holds them equal).
    focal_fwd_tile_a: int = 8192
    focal_bwd_tile_a: int = 4096


def _focal_elementwise(
    logits: jnp.ndarray, targets: jnp.ndarray, config: LossConfig
) -> jnp.ndarray:
    """Per-element focal terms (same shape as ``logits``); f32 in/out.

    Exponential form — 2 transcendentals/element instead of ~5.  With
    sp_neg = softplus(-x) = -log p and sp_neg + x*t ∈ {sp_neg, softplus(x)}:
      bce        = -log p_t       = softplus(x) - x*t  (= sp_neg + x - x*t)
      (1-p_t)^γ  = exp(γ log(1-p_t)) = exp(-γ (sp_neg + x*t))
    Both factors come from ONE softplus and ONE exp; the VPU-bound focal
    op is transcendental-limited, so this halves its step cost (measured
    ~6.2ms → see ops/pallas/focal.py for the numbers at the flagship bucket).
    """
    sp_neg = nn.softplus(-logits)
    xt = logits * targets
    bce = sp_neg + logits - xt  # == softplus(x) - x*t, stable for any x
    modulator = jnp.exp(-config.focal_gamma * (sp_neg + xt))
    alpha_t = config.focal_alpha * targets + (1.0 - config.focal_alpha) * (
        1.0 - targets
    )
    return alpha_t * modulator * bce


def focal_sums(
    cls_logits: jnp.ndarray,
    cls_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> jnp.ndarray:
    """Per-image focal sums (...,) over non-ignored anchors — no normalizer.

    The additive core shared by :func:`focal_loss` and the per-level path
    (:func:`total_loss_compact_levels`): sums over different anchor subsets
    simply add.
    """
    logits = cls_logits.astype(jnp.float32)
    targets = cls_targets.astype(jnp.float32)
    loss = _focal_elementwise(logits, targets, config)  # (..., A, K)

    not_ignored = (anchor_state != matching.IGNORE).astype(jnp.float32)
    loss = loss * not_ignored[..., None]
    return jnp.sum(loss, axis=(-2, -1))


def focal_loss(
    cls_logits: jnp.ndarray,
    cls_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> jnp.ndarray:
    """Scalar focal loss.

    Args:
      cls_logits: (..., A, K) raw logits.
      cls_targets: (..., A, K) one-hot targets (all-zero rows for negatives).
      anchor_state: (..., A) in {-1 ignore, 0 negative, 1 positive}.
    """
    # Per-image normalization then batch mean (paper semantics, DP-invariant;
    # deliberate divergence from keras-retinanet — see module docstring).
    return _normalize_per_image(
        focal_sums(cls_logits, cls_targets, anchor_state, config), anchor_state
    )


def focal_loss_compact(
    cls_logits: jnp.ndarray,
    matched_labels: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> jnp.ndarray:
    """Focal loss from integer labels — no dense one-hot target tensor.

    Mathematically identical to :func:`focal_loss` with
    ``cls_targets = one_hot(matched_labels) * (state == POSITIVE)``, but the
    one-hot is an implicit ``labels == iota(K)`` compare that XLA fuses into
    the elementwise focal computation.  At the flagship bucket this removes a
    (B, 201600, 80) f32 target tensor (~0.5 GB of HBM writes+reads per step)
    from the hot path — the train step consumes this form.

    Args:
      cls_logits: (..., A, K) raw logits.
      matched_labels: (..., A) int32 matched class ids (only read where
        positive).
      anchor_state: (..., A) in {-1 ignore, 0 negative, 1 positive}.
    """
    if config.pallas_focal:
        from batchai_retinanet_horovod_coco_tpu.ops.pallas import (
            focal_loss_per_image_sums,
        )

        # The kernel is written for (B, A, K); flatten any leading dims into
        # B (and add one for unbatched input) to honor the (..., A, K)
        # contract of this function.
        a, k = cls_logits.shape[-2:]
        sums = focal_loss_per_image_sums(
            cls_logits.reshape(-1, a, k),
            matched_labels.astype(jnp.int32).reshape(-1, a),
            anchor_state.astype(jnp.int32).reshape(-1, a),
            config.focal_alpha,
            config.focal_gamma,
            config.pallas_interpret,
            config.focal_fwd_tile_a,
            config.focal_bwd_tile_a,
        )
        return _normalize_per_image(
            sums.reshape(anchor_state.shape[:-1]), anchor_state
        )

    return _normalize_per_image(
        focal_sums_compact(cls_logits, matched_labels, anchor_state, config),
        anchor_state,
    )


def focal_sums_compact(
    cls_logits: jnp.ndarray,
    matched_labels: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> jnp.ndarray:
    """Per-image focal sums from integer labels (implicit one-hot)."""
    num_classes = cls_logits.shape[-1]
    targets = (
        (anchor_state == matching.POSITIVE)[..., None]
        & (
            matched_labels[..., None]
            == jnp.arange(num_classes, dtype=jnp.int32)
        )
    ).astype(jnp.float32)
    return focal_sums(cls_logits, targets, anchor_state, config)


def _smooth_l1_elementwise(
    preds: jnp.ndarray, targets: jnp.ndarray, config: LossConfig
) -> jnp.ndarray:
    """Per-element smooth-L1 terms (f32 in/out) — the single definition
    shared by the anchor-major and NHWC paths."""
    diff = jnp.abs(preds - targets)
    beta = config.smooth_l1_beta
    return jnp.where(diff < beta, 0.5 * diff * diff / beta, diff - 0.5 * beta)


def smooth_l1_sums(
    box_preds: jnp.ndarray,
    box_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> jnp.ndarray:
    """Per-image smooth-L1 sums (...,) over positive anchors — no normalizer."""
    loss = _smooth_l1_elementwise(
        box_preds.astype(jnp.float32), box_targets.astype(jnp.float32), config
    )
    positive = (anchor_state == matching.POSITIVE).astype(jnp.float32)
    loss = loss * positive[..., None]
    return jnp.sum(loss, axis=(-2, -1))


def smooth_l1_loss(
    box_preds: jnp.ndarray,
    box_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> jnp.ndarray:
    """Scalar smooth-L1 regression loss over positive anchors.

    Args:
      box_preds: (..., A, 4) predicted deltas.
      box_targets: (..., A, 4) encoded target deltas.
      anchor_state: (..., A).
    """
    # Per-image normalization, then batch mean (see focal_loss).
    return _normalize_per_image(
        smooth_l1_sums(box_preds, box_targets, anchor_state, config),
        anchor_state,
    )


def total_loss_compact_levels(
    cls_levels: tuple[jnp.ndarray, ...],
    box_levels: tuple[jnp.ndarray, ...],
    matched_labels: jnp.ndarray,
    box_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> dict[str, jnp.ndarray]:
    """:func:`total_loss_compact` on PER-LEVEL head outputs.

    Consumes the raw per-pyramid-level (B, A_l, K)/(B, A_l, 4) head outputs
    instead of their concatenation, slicing the (cheap, (B, A)-shaped)
    targets to match.  Per-image sums add across levels; normalization
    happens once at the end, so the result equals :func:`total_loss_compact`
    on the concatenated outputs up to f32 summation order.

    MEASURED (v5e-1, flagship bucket): the step is ~1.3% SLOWER this way
    (57.7 vs 58.4 imgs/s) — XLA already folds the concat/split into
    adjacent fusions, and five per-level loss kernel groups (P6/P7 are
    tiny) cost more than the one fused pass.  The train step therefore
    keeps the concatenated form; this entrypoint stays for workloads with
    fewer/larger levels and as the consumer of a future NHWC-direct head
    output.
    """
    if config.pallas_focal:
        raise ValueError(
            "pallas_focal is not routed through the per-level path; use "
            "total_loss_compact (concatenated) with it"
        )
    covered = sum(c.shape[-2] for c in cls_levels)
    if covered != anchor_state.shape[-1]:
        # Checked BEFORE slicing: Python slices clamp, so over-coverage
        # would otherwise surface as an opaque broadcast error mid-loop.
        raise ValueError(
            f"level outputs cover {covered} anchors, targets have "
            f"{anchor_state.shape[-1]}"
        )
    cls_sum = jnp.zeros(anchor_state.shape[:-1], jnp.float32)
    box_sum = jnp.zeros(anchor_state.shape[:-1], jnp.float32)
    offset = 0
    for cls_l, box_l in zip(cls_levels, box_levels, strict=True):
        num = cls_l.shape[-2]
        sl = slice(offset, offset + num)
        offset += num
        cls_sum = cls_sum + focal_sums_compact(
            cls_l, matched_labels[..., sl], anchor_state[..., sl], config
        )
        box_sum = box_sum + smooth_l1_sums(
            box_l, box_targets[..., sl, :], anchor_state[..., sl], config
        )
    cls = _normalize_per_image(cls_sum, anchor_state)
    box = _normalize_per_image(box_sum, anchor_state)
    return {
        "loss": cls + config.box_loss_weight * box,
        "cls_loss": cls,
        "box_loss": box,
    }


def _focal_nhwc_elementwise(
    logits: jnp.ndarray, t_ck: jnp.ndarray, alpha: float, gamma: float
) -> jnp.ndarray:
    """Per-element focal terms from f32 logits and a BOOL target mask."""
    sp_neg = nn.softplus(-logits)
    xt = jnp.where(t_ck, logits, 0.0)
    bce = sp_neg + logits - xt
    modulator = jnp.exp(-gamma * (sp_neg + xt))
    alpha_t = jnp.where(t_ck, alpha, 1.0 - alpha)
    return alpha_t * modulator * bce


def _nhwc_masks(
    labels4: jnp.ndarray,
    state4: jnp.ndarray,
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(t_ck, ni_ck) bool masks in the (B, h, w, A*K) channel layout.

    The A → A·K broadcast runs as ONE tiny matmul on the MXU: targets are
    encoded per anchor as e = label (positive) / k (negative) / k+1
    (ignore) — with k <= 255 every value is <= 256, so bf16 is exact, and
    each output column picks exactly one input (no accumulation) — and
    e @ R with the static 0/1 replication matrix R lands e in the
    (B, h, w, A·K) lane layout.
    The obvious broadcast-reshape forms all materialize worse: XLA cannot
    bitcast a (B, h, w, A, K)-broadcast into the 4-D lane tiling, so it
    materialized the compare's operand at full size (387 MB s32 per
    P3-sized level); measured per round-3 microbench (fwd+bwd focal sums,
    flagship shapes): 5-D reshape 4.6 ms, static-take 4.2 ms, this 2.7 ms.
    """
    lead = labels4.shape[:-1]
    a_loc = labels4.shape[-1]
    ck = a_loc * k
    if k > 255:
        # bf16 represents integers exactly only up to 256; fall back to the
        # broadcast-reshape form for very wide class counts.
        positive4 = state4 == matching.POSITIVE
        t_ck = (
            positive4[..., None]
            & (labels4[..., None] == jnp.arange(k, dtype=jnp.int32))
        ).reshape(*lead, ck)
        ni_ck = jnp.broadcast_to(
            (state4 != matching.IGNORE)[..., None], (*lead, a_loc, k)
        ).reshape(*lead, ck)
        return t_ck, ni_ck
    return _masks_from_encode(_nhwc_encode(labels4, state4, k), k)


def _nhwc_encode(
    labels4: jnp.ndarray, state4: jnp.ndarray, k: int
) -> jnp.ndarray:
    """The encoded-target matmul broadcast: (B, h, w, A) → (B, h, w, A·K)
    bf16 ``e`` with e = label / k (negative) / k+1 (ignore).  Requires
    k <= 255 (see _nhwc_masks)."""
    lead = labels4.shape[:-1]
    a_loc = labels4.shape[-1]
    ck = a_loc * k
    neg, ign = float(k), float(k + 1)  # sentinels outside the label range
    rep = np.zeros((a_loc, ck), np.float32)
    for a in range(a_loc):
        rep[a, a * k : (a + 1) * k] = 1.0
    rep = jnp.asarray(rep, dtype=jnp.bfloat16)
    e = jnp.where(
        state4 == matching.POSITIVE,
        labels4.astype(jnp.float32),
        jnp.where(state4 == matching.IGNORE, ign, neg),
    )
    return (e.astype(jnp.bfloat16).reshape(-1, a_loc) @ rep).reshape(*lead, ck)


def _masks_from_encode(
    e_ck: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    k_idx = jnp.asarray(np.arange(e_ck.shape[-1]) % k, dtype=jnp.bfloat16)
    return e_ck == k_idx, e_ck != float(k + 1)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _focal_nhwc_level_sums(
    cls_l: jnp.ndarray,
    labels4: jnp.ndarray,
    state4: jnp.ndarray,
    alpha: float,
    gamma: float,
) -> jnp.ndarray:
    """Per-image focal sums for ONE level of raw (B, h, w, A*K) head output.

    ``labels4``/``state4`` are the (B, h, w, A) per-location targets.  The
    hand-written VJP is the point: JAX autodiff of the focal expression saves
    several full-size f32 residuals (softplus, modulator, bce — ~0.5 GB each
    at the flagship P3 level) for the backward pass, which made the loss
    slice HBM-bound (~6.4 ms fwd+bwd measured in isolation at the flagship
    bucket).  Here backward recomputes the cheap transcendentals from the
    saved bf16 logits in ONE fused pass whose only big output is d(logits) —
    measured 2.9 ms fwd+bwd for the same shapes, and bitwise-identical
    forward values (same expression graph).
    """
    t_ck, ni_ck = _nhwc_masks(labels4, state4, cls_l.shape[-1] // labels4.shape[-1])
    fl = _focal_nhwc_elementwise(cls_l.astype(jnp.float32), t_ck, alpha, gamma)
    return jnp.sum(jnp.where(ni_ck, fl, 0.0), axis=(-3, -2, -1))


def _focal_nhwc_level_sums_fwd(cls_l, labels4, state4, alpha, gamma):
    k = cls_l.shape[-1] // labels4.shape[-1]
    if k > 255:
        out = _focal_nhwc_level_sums(cls_l, labels4, state4, alpha, gamma)
        return out, (cls_l, labels4, state4, None)
    # Save the bf16 encoded-target tensor as the residual: backward reads
    # it instead of re-running the mask matmul (one 258 MB read vs
    # dot + write + read at the flagship bucket).
    e_ck = _nhwc_encode(labels4, state4, k)
    t_ck, ni_ck = _masks_from_encode(e_ck, k)
    fl = _focal_nhwc_elementwise(cls_l.astype(jnp.float32), t_ck, alpha, gamma)
    out = jnp.sum(jnp.where(ni_ck, fl, 0.0), axis=(-3, -2, -1))
    # state4 is NOT a residual on this path (backward only needs its shape,
    # == labels4's, for the float0 cotangent) — holding it would keep dead
    # bytes alive across the whole backbone backward.
    return out, (cls_l, labels4, None, e_ck)


def _focal_nhwc_level_sums_bwd(alpha, gamma, res, g):
    cls_l, labels4, state4, e_ck = res
    k = cls_l.shape[-1] // labels4.shape[-1]
    if e_ck is None:
        t_ck, ni_ck = _nhwc_masks(labels4, state4, k)
    else:
        t_ck, ni_ck = _masks_from_encode(e_ck, k)
    x = cls_l.astype(jnp.float32)
    # d f / d x in closed form, one fused elementwise pass:
    #   s = sigmoid(x), spn = softplus(-x), spp = softplus(x)
    #   t=0: f = (1-a)·exp(-g·spn)·spp  →  f' = (1-a)·exp(-g·spn)·(g(1-s)spp + s)
    #   t=1: f = a·exp(-g·spp)·spn      →  f' = -a·exp(-g·spp)·(g·s·spn + 1 - s)
    s = nn.sigmoid(x)
    spn = nn.softplus(-x)
    spp = spn + x  # == softplus(x), stable for any x
    d_neg = (1.0 - alpha) * jnp.exp(-gamma * spn) * (gamma * (1.0 - s) * spp + s)
    d_pos = -alpha * jnp.exp(-gamma * spp) * (gamma * s * spn + 1.0 - s)
    df = jnp.where(ni_ck, jnp.where(t_ck, d_pos, d_neg), 0.0)
    # g has the per-image shape (...,); broadcast over (h, w, ck).
    dcls = (g[..., None, None, None] * df).astype(cls_l.dtype)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # int-array cotangents
    return dcls, f0(labels4), f0(labels4)  # state4 shares labels4's shape


_focal_nhwc_level_sums.defvjp(_focal_nhwc_level_sums_fwd, _focal_nhwc_level_sums_bwd)


def total_loss_compact_nhwc(
    cls_levels: tuple[jnp.ndarray, ...],
    box_levels: tuple[jnp.ndarray, ...],
    matched_labels: jnp.ndarray,
    box_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    anchors_per_location: int,
    config: LossConfig = LossConfig(),
    planar_box_targets: bool = False,
) -> dict[str, jnp.ndarray]:
    """:func:`total_loss_compact` on RAW (B, h, w, A·K) head outputs.

    The anchor-major path retiles every level's lane dimension
    (A·K → K-minor), concatenates, and splits again in the backward pass —
    ~4 ms of pure layout traffic at the flagship bucket (round-3 profile:
    reshape.419/483 + concatenate.7 + split.1).  Here the big tensors stay
    in their conv-native layout end-to-end: the per-level target slices are
    the only retiled arrays ((B, A_l) int32/int8 — a few MB), and the view
    reshapes on the head outputs feed straight into the fused elementwise
    focal/smooth-L1 + reduction, so XLA never materializes them.  Equals
    :func:`total_loss_compact` on the concatenated outputs up to f32
    summation order (pinned by a unit test).
    """
    if config.pallas_focal:
        raise ValueError(
            "pallas_focal is not routed through the NHWC path; use "
            "total_loss_compact (concatenated) with it"
        )
    a_loc = anchors_per_location
    covered = sum(c.shape[1] * c.shape[2] * a_loc for c in cls_levels)
    if covered != anchor_state.shape[-1]:
        raise ValueError(
            f"level outputs cover {covered} anchors, targets have "
            f"{anchor_state.shape[-1]}"
        )
    batch_shape = anchor_state.shape[:-1]
    cls_sum = jnp.zeros(batch_shape, jnp.float32)
    box_sum = jnp.zeros(batch_shape, jnp.float32)
    offset = 0
    for cls_l, box_l in zip(cls_levels, box_levels, strict=True):
        b, h, w, ck = cls_l.shape
        k = ck // a_loc
        n = h * w * a_loc
        sl = slice(offset, offset + n)
        offset += n
        # Per-level targets, reshaped on the SMALL side only ((B, A_l)
        # ints and the (B, A_l, 4) box targets — a few MB).  The big head
        # tensors are never split into (A, K)/(A, 4) views: a 4-minor-dim
        # view of a (B, h, w, 36) tensor retiles it catastrophically
        # (measured: the first nhwc attempt moved ~7 ms of retile cost
        # INTO the loss).  Instead the masks/targets broadcast-reshape
        # from (B, h, w, A) up to the A·K channel layout (``_nhwc_masks``)
        # — bool through any materialization XLA decides on.  The focal
        # term uses the hand-VJP level kernel: autodiff residuals were
        # the dominant loss cost (see ``_focal_nhwc_level_sums``).
        labels4 = matched_labels[..., sl].reshape(*batch_shape, h, w, a_loc)
        state4 = anchor_state[..., sl].reshape(*batch_shape, h, w, a_loc)
        positive4 = state4 == matching.POSITIVE
        cls_sum = cls_sum + _focal_nhwc_level_sums(
            cls_l, labels4, state4, config.focal_alpha, config.focal_gamma
        )

        c4 = a_loc * 4
        if planar_box_targets:
            # (..., 4, A) planar targets: slice lanes, then one SMALL
            # transpose (a few MB, dense tiles) into the (a, j) channel
            # order of the head output.  The (..., A, 4) form instead
            # retiles a 32x-lane-padded tensor (~1 ms for P3 alone,
            # round-3 profile reshape.488).
            boxt_ck = (
                jnp.moveaxis(
                    box_targets[..., sl].reshape(
                        *batch_shape, 4, h, w, a_loc
                    ),
                    -4,
                    -1,
                )
                .reshape(*batch_shape, h, w, c4)
                .astype(jnp.float32)
            )
        else:
            boxt_ck = (
                box_targets[..., sl, :]
                .reshape(*batch_shape, h, w, c4)
                .astype(jnp.float32)
            )
        sl1 = _smooth_l1_elementwise(box_l.astype(jnp.float32), boxt_ck, config)
        pos_ck = jnp.broadcast_to(
            positive4[..., None], (*batch_shape, h, w, a_loc, 4)
        ).reshape(*batch_shape, h, w, c4)
        box_sum = box_sum + jnp.sum(
            jnp.where(pos_ck, sl1, 0.0), axis=(-3, -2, -1)
        )
    cls = _normalize_per_image(cls_sum, anchor_state)
    box = _normalize_per_image(box_sum, anchor_state)
    return {
        "loss": cls + config.box_loss_weight * box,
        "cls_loss": cls,
        "box_loss": box,
    }


def total_loss_compact(
    cls_logits: jnp.ndarray,
    box_preds: jnp.ndarray,
    matched_labels: jnp.ndarray,
    box_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> dict[str, jnp.ndarray]:
    """:func:`total_loss` on compact (integer-label) targets — the step path."""
    cls = focal_loss_compact(cls_logits, matched_labels, anchor_state, config)
    box = smooth_l1_loss(box_preds, box_targets, anchor_state, config)
    return {
        "loss": cls + config.box_loss_weight * box,
        "cls_loss": cls,
        "box_loss": box,
    }


def total_loss(
    cls_logits: jnp.ndarray,
    box_preds: jnp.ndarray,
    cls_targets: jnp.ndarray,
    box_targets: jnp.ndarray,
    anchor_state: jnp.ndarray,
    config: LossConfig = LossConfig(),
) -> dict[str, jnp.ndarray]:
    cls = focal_loss(cls_logits, cls_targets, anchor_state, config)
    box = smooth_l1_loss(box_preds, box_targets, anchor_state, config)
    return {
        "loss": cls + config.box_loss_weight * box,
        "cls_loss": cls,
        "box_loss": box,
    }
