"""Fused focal-loss Pallas kernel (forward + custom VJP) — opt-in.

An alternative lowering of ``losses.focal_loss_compact`` on TPU.  It does
one read of the logits per direction:

- forward: one pass computing the per-image masked focal sum directly
  (nothing materialized except a (B, 1) output);
- backward: one pass recomputing p from the logits and emitting
  d(loss_sum_b)/d(logits) scaled by the incoming cotangent — no residuals
  beyond the inputs themselves.

The implicit one-hot target ``(state == POSITIVE) & (label == k)`` is
reconstructed inside the kernel from the integer labels (same contract as
``losses.focal_loss_compact``).  Normalization (per-image /num_pos, batch
mean) stays outside — it is (B,)-shaped math.

Closed-form gradient (p = sigmoid(x), per element):
  t=1:  alpha   * (1-p)^gamma * (gamma * p * log(p) + p - 1)
  t=0:  (1-a)   * p^gamma     * (p - gamma * (1-p) * log(1-p))
with log(p) = -softplus(-x), log(1-p) = -softplus(x) for stability.
Validated against jax.grad of the jnp implementation in
tests/unit/test_pallas_focal.py.

MEASURED (v5e-1, flagship bucket B=8, A=201600, K=80, f32): this kernel is
SLOWER than XLA's lowering of the exp-form jnp path — 7.9 vs 3.6 ms forward,
12.7 vs 4.5 ms fwd+bwd — because K=80 occupies only 80 of 128 VPU lanes in
every (TILE_A, K) block (37% waste) and the (1, TILE_A, 80) HBM->VMEM DMAs
pipeline worse than XLA's chosen layout.  It is therefore OFF by default
(``LossConfig.pallas_focal``); kept, tested, and wired for workloads with
K >= 128 where the lane padding vanishes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Anchor-tile sizes, bounded by the ~16MB scoped-vmem budget: each live
# (TILE_A, 80) f32 temp is TILE_A*80*4 bytes, and the backward kernel holds
# more of them at once (grad output + recomputed p/log terms), so it tiles
# smaller.  8192 OOMs backward at K=80 (19.5M scoped); 4096 fits.
FWD_TILE_A = 8192
BWD_TILE_A = 4096


def _masked_target(labels, state, shape_ak):
    """Implicit one-hot: (TILE_A, K) bool target + (TILE_A, 1) row mask.

    All broadcasts happen on 2-D int32 values — Mosaic only supports
    inserting a minor dim on 32-bit types, so the bool compares come after
    the [:, None] expansion, never before.
    """
    kcol = jax.lax.broadcasted_iota(jnp.int32, shape_ak, 1)
    labels2 = labels[:, None]  # (TILE_A, 1) int32
    state2 = state[:, None]
    t = (state2 == 1) & (labels2 == kcol)
    not_ignored = state2 != -1  # (TILE_A, 1)
    return t, not_ignored


def _fwd_kernel(labels_ref, state_ref, logits_ref, out_ref, *, alpha, gamma, num_anchors):
    tile = pl.program_id(1)
    x = logits_ref[0].astype(jnp.float32)  # (TILE_A, K)
    labels = labels_ref[0, 0]  # (TILE_A,)
    state = state_ref[0, 0]

    t, not_ignored = _masked_target(labels, state, x.shape)
    row = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    in_range = (tile * x.shape[0] + row) < num_anchors  # (TILE_A, 1)
    valid = not_ignored & in_range

    # Exponential form (see losses.focal_loss): bce = sp_pos - x*t,
    # (1-p_t)^gamma = exp(-gamma*(sp_neg + x*t)) — one softplus + one exp.
    sp_neg = nn.softplus(-x)
    xt = jnp.where(t, x, 0.0)
    bce = sp_neg + x - xt
    modulator = jnp.exp(-gamma * (sp_neg + xt))
    alpha_t = jnp.where(t, alpha, 1.0 - alpha)
    loss = alpha_t * modulator * bce
    partial = jnp.sum(jnp.where(valid, loss, 0.0))

    @pl.when(tile == 0)
    def _():
        out_ref[0, 0, 0] = 0.0

    out_ref[0, 0, 0] += partial


def _bwd_kernel(
    labels_ref, state_ref, logits_ref, g_ref, dx_ref, *, alpha, gamma, num_anchors
):
    tile = pl.program_id(1)
    x = logits_ref[0].astype(jnp.float32)
    labels = labels_ref[0, 0]  # (TILE_A,)
    state = state_ref[0, 0]
    g = g_ref[0, 0, 0]

    t, not_ignored = _masked_target(labels, state, x.shape)
    row = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    in_range = (tile * x.shape[0] + row) < num_anchors  # (TILE_A, 1)
    valid = not_ignored & in_range

    # Exponential form of the closed-form gradient (docstring): with
    # sp_neg = -log p, sp_pos = -log(1-p), p = exp(-sp_neg):
    #   t=1:  alpha   * exp(-g*sp_pos) * (p - 1 - g*p*sp_neg)
    #   t=0:  (1-a)   * exp(-g*sp_neg) * (p + g*(1-p)*sp_pos)
    # and exp(-g*(sp_neg + x*t)) covers both modulators in one exp.
    sp_neg = nn.softplus(-x)
    sp_pos = x + sp_neg
    xt = jnp.where(t, x, 0.0)
    modulator = jnp.exp(-gamma * (sp_neg + xt))
    p = jnp.exp(-sp_neg)
    inner = jnp.where(
        t, p - 1.0 - gamma * p * sp_neg, p + gamma * (1.0 - p) * sp_pos
    )
    alpha_t = jnp.where(t, alpha, 1.0 - alpha)
    grad = alpha_t * modulator * inner
    grad = jnp.where(valid, grad, 0.0) * g
    dx_ref[0] = grad.astype(dx_ref.dtype)


def _row_spec(tile_a):
    # labels/state ship as (B, 1, A): rank-3 so the BLOCKED last-two dims are
    # (1, TILE_A) — legal Mosaic tiling (1 == full middle dim, TILE_A % 128
    # == 0) — while a rank-2 (B, A) block of (1, TILE_A) is rejected.
    return pl.BlockSpec(
        (1, 1, tile_a), lambda b, t: (b, 0, t), memory_space=pltpu.VMEM
    )


def _call_fwd(
    cls_logits, matched_labels, anchor_state, alpha, gamma, interpret,
    tile_a=None,
):
    tile = FWD_TILE_A if tile_a is None else int(tile_a)
    batch, num_anchors, _ = cls_logits.shape
    grid = (batch, pl.cdiv(num_anchors, tile))
    out = pl.pallas_call(
        functools.partial(
            _fwd_kernel, alpha=alpha, gamma=gamma, num_anchors=num_anchors
        ),
        grid=grid,
        in_specs=[
            _row_spec(tile),
            _row_spec(tile),
            pl.BlockSpec(
                (1, tile, cls_logits.shape[-1]),
                lambda b, t: (b, t, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1), lambda b, t: (b, 0, 0), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((batch, 1, 1), jnp.float32),
        # allow_input_fusion on the logits: the producer (per-level head
        # outputs transposed+concatenated to (B, A, K)) fuses into the kernel
        # instead of materializing in HBM — the whole point of fusing focal.
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            allow_input_fusion=[False, False, True],
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(matched_labels[:, None, :], anchor_state[:, None, :], cls_logits)
    return out[:, 0, 0]


def _call_bwd(
    cls_logits, matched_labels, anchor_state, g, alpha, gamma, interpret,
    tile_a=None,
):
    tile = BWD_TILE_A if tile_a is None else int(tile_a)
    batch, num_anchors, _ = cls_logits.shape
    grid = (batch, pl.cdiv(num_anchors, tile))
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, alpha=alpha, gamma=gamma, num_anchors=num_anchors
        ),
        grid=grid,
        in_specs=[
            _row_spec(tile),
            _row_spec(tile),
            pl.BlockSpec(
                (1, tile, cls_logits.shape[-1]),
                lambda b, t: (b, t, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, 1), lambda b, t: (b, 0, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, tile, cls_logits.shape[-1]),
            lambda b, t: (b, t, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(cls_logits.shape, cls_logits.dtype),
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            allow_input_fusion=[False, False, True, False],
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(
        matched_labels[:, None, :],
        anchor_state[:, None, :],
        cls_logits,
        g.reshape(batch, 1, 1).astype(jnp.float32),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def focal_loss_per_image_sums(
    cls_logits: jnp.ndarray,
    matched_labels: jnp.ndarray,
    anchor_state: jnp.ndarray,
    alpha: float = 0.25,
    gamma: float = 2.0,
    interpret: bool = False,
    fwd_tile_a: int | None = None,
    bwd_tile_a: int | None = None,
) -> jnp.ndarray:
    """Per-image focal-loss sums (B,) over non-ignored anchors, fused on TPU.

    Args:
      cls_logits: (B, A, K) raw logits (any float dtype; computed in f32).
      matched_labels: (B, A) int32 matched class ids (read where positive).
      anchor_state: (B, A) int32 in {-1 ignore, 0 negative, 1 positive}.
      interpret: run the kernel in interpreter mode (CPU testing).
      fwd_tile_a / bwd_tile_a: anchor-tile widths (None = the module
        defaults FWD_TILE_A/BWD_TILE_A) — must be positive multiples of
        128; the backward ceiling is lower because it holds more live
        temps (see the constants' note above).

    Gradients flow to ``cls_logits`` only.
    """
    return _call_fwd(
        cls_logits, matched_labels, anchor_state, alpha, gamma, interpret,
        fwd_tile_a,
    )


def _vjp_fwd(
    cls_logits, matched_labels, anchor_state, alpha, gamma, interpret,
    fwd_tile_a, bwd_tile_a,
):
    out = _call_fwd(
        cls_logits, matched_labels, anchor_state, alpha, gamma, interpret,
        fwd_tile_a,
    )
    return out, (cls_logits, matched_labels, anchor_state)


def _vjp_bwd(
    alpha, gamma, interpret, fwd_tile_a, bwd_tile_a, residuals, g
):
    cls_logits, matched_labels, anchor_state = residuals
    dx = _call_bwd(
        cls_logits, matched_labels, anchor_state, g, alpha, gamma, interpret,
        bwd_tile_a,
    )
    return dx, None, None


focal_loss_per_image_sums.defvjp(_vjp_fwd, _vjp_bwd)
