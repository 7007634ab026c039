"""The mixers' depthwise convolution of ops/document_conv.py as a Pallas TPU kernel pair.

    pre_t = b + sum over j < taps of w[taps - 1 - j] * x_{t-j}   (x_{t-j} counted where token t - j is in t's document)
    y_t   = silu(pre_t)

The XLA lowering makes ``taps`` shifted, masked float32 copies of ``x`` and, in the
backward pass, transposes each pad, slice and select and reduces every tap's gradient
over the tokens on its own.  Here a block of channels x a block of tokens is read
once in ``x``'s dtype with the lane tile of tokens before it (the backward: and the
one after it), the shifts are lane rolls in VMEM, and the block's result is written
once.

Layout: the tokens are LAST, (batch, channels, tokens): channels on the sublanes,
tokens on the lanes.  That is how XLA holds a mixer's activations on a TPU (the
scans' kernels, ops/pallas/ssd.py and ops/pallas/delta_rule.py, read tokens-last
operands, and ``in_proj``'s product is laid out for them): a kernel over (tokens,
channels) made every mixer pay transposes around the scan (MEASURED in
ops/document_conv.py).  A tap is a column, a token's mask a row.  The masks come as
ONE int32 a token (``same``, made by the caller from the segment ids): bit j says
that token t - j exists and lies in t's document.

The backward kernel keeps nothing of the forward: from ``x``, ``w``, ``b`` and the
masks it forms ``pre`` again on its block and the lane tile after it, ``dpre = dy *
silu'(pre)`` there, and

    dx_s  = sum over j of w[taps - 1 - j] * dpre_{s+j}         (where s lies in s + j's document)
    dw[taps - 1 - j] = sum over t of dpre_t * x_{t-j}          (the same mask), db = sum over t of dpre_t

``dw`` and ``db`` are float32 blocks that stay in VMEM while a block of channels
walks every sequence's token blocks (the grid is channels x batch x tokens, the
last two in order).

Precision, as the XLA body: the input converted to float32 first, float32 taps,
float32 sum in the body's order (the tap on the token itself first), float32 SiLU; a
token of another document enters as an exact 0 (a ``where``, not a product).  The
result is float32, the function's contract (ops/document_conv.py); ``dx`` is rounded
once, to ``x``'s dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # what a block sees of its neighbour: one lane tile of tokens, so ``taps - 1 <= LANES``

# Tokens and channels (at most) of one grid step.  MEASURED: ops/document_conv.py.
TOKEN_BLOCK = 2048
CHANNEL_BLOCK = 256


def channel_block(channels: int, at_most: int = CHANNEL_BLOCK) -> int:
    """The tallest block of whole sublane tiles (16 rows of bfloat16) that divides ``channels`` and
    is no taller than ``at_most``."""
    tiles = channels // 16
    return 16 * max(d for d in range(1, tiles + 1) if tiles % d == 0 and 16 * d <= at_most)


def _bit(same, j):
    return (same >> j) & 1 == 1


def _shifted(xx, same, j, tokens):
    """Columns ``LANES .. LANES + tokens`` of ``xx`` delayed by ``j`` tokens, 0 where ``same`` says so."""
    if j == 0:
        return xx[:, LANES:LANES + tokens]
    return jnp.where(_bit(same, j), pltpu.roll(xx, j, 1)[:, LANES:LANES + tokens], 0.0)


def _pre(shifted, w_ref, b_ref, taps):
    acc = w_ref[:, taps - 1:taps] * shifted[0]
    for j in range(1, taps):
        acc = acc + w_ref[:, taps - 1 - j:taps - j] * shifted[j]
    return b_ref[...] + acc


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, same_ref, y_ref, *, taps):
    x = x_ref[0].astype(jnp.float32)  # (channels, tokens)
    # the first block's "tokens before" are its own: every bit that would reach them is 0
    xx = jnp.concatenate([before_ref[0].astype(jnp.float32), x], axis=1)
    same = same_ref[0]  # (1, tokens)
    pre = _pre([_shifted(xx, same, j, x.shape[1]) for j in range(taps)], w_ref, b_ref, taps)
    y_ref[0] = pre * jax.nn.sigmoid(pre)


def _bwd_kernel(x_ref, before_ref, w_ref, b_ref, same_ref, after_ref, same_after_ref, dy_ref, dy_after_ref,
                dx_ref, dw_ref, db_ref, *, taps):
    seq, block = pl.program_id(1), pl.program_id(2)
    tokens = x_ref.shape[2]
    wide = tokens + LANES  # the block's tokens and the lane tile after them
    f32 = lambda ref: ref[0].astype(jnp.float32)
    xx = jnp.concatenate([f32(before_ref), f32(x_ref), f32(after_ref)], axis=1)
    # The last block's "tokens after" are its own again: no token follows, so nothing reaches back from them.
    same_after = jnp.where(block == pl.num_programs(2) - 1, 0, same_after_ref[0])
    same = jnp.concatenate([same_ref[0], same_after], axis=1)
    dy = jnp.concatenate([f32(dy_ref), f32(dy_after_ref)], axis=1)

    shifted = [_shifted(xx, same, j, wide) for j in range(taps)]
    pre = _pre(shifted, w_ref, b_ref, taps)
    s = jax.nn.sigmoid(pre)
    dpre = dy * (s * (1.0 + pre * (1.0 - s)))  # (channels, wide)

    dx = w_ref[:, taps - 1:taps] * dpre[:, :tokens]
    for j in range(1, taps):  # token s receives from s + j: the anti-causal counterpart
        reaches = jnp.where(_bit(same, j), dpre, 0.0)
        dx = dx + w_ref[:, taps - 1 - j:taps - j] * pltpu.roll(reaches, wide - j, 1)[:, :tokens]
    dx_ref[0] = dx.astype(dx_ref.dtype)

    @pl.when((seq == 0) & (block == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    own = dpre[:, :tokens]
    for j in range(taps):
        dw_ref[:, taps - 1 - j:taps - j] += jnp.sum(own * shifted[j][:, :tokens], axis=1, keepdims=True)
    db_ref[...] += jnp.sum(own, axis=1, keepdims=True)


def _taps_last(w):
    """(channels, taps padded to whole sublane tiles' worth of lanes) float32: a tap is a column."""
    return jnp.pad(w.astype(jnp.float32).T, [(0, 0), (0, -w.shape[0] % 8)])


def _call(kernel, x, w, b, same, more, blocks, interpret, *, name, semantics, out_specs, out_shape):
    """``kernel`` over the grid (block of channels, batch, block of tokens) on ``x`` with the lane tile before
    it, the taps, the bias, the masks, and ``more`` operands by spec name; ``out_specs`` by name."""
    batch, channels, t = x.shape
    tb, cb = blocks or (TOKEN_BLOCK, CHANNEL_BLOCK)
    cb = channel_block(channels, cb)
    per, last = tb // LANES, t // LANES - 1
    before = lambda i: jnp.maximum(i * per - 1, 0)
    after = lambda i: jnp.minimum((i + 1) * per, last)
    wt = _taps_last(w)
    specs = dict(
        own=pl.BlockSpec((1, cb, tb), lambda c, b, i: (b, c, i)),
        before=pl.BlockSpec((1, cb, LANES), lambda c, b, i: (b, c, before(i))),
        after=pl.BlockSpec((1, cb, LANES), lambda c, b, i: (b, c, after(i))),
        taps=pl.BlockSpec((cb, wt.shape[1]), lambda c, b, i: (c, 0)),
        bias=pl.BlockSpec((cb, 1), lambda c, b, i: (c, 0)),
        same=pl.BlockSpec((1, 1, tb), lambda c, b, i: (b, 0, i)),
        same_after=pl.BlockSpec((1, 1, LANES), lambda c, b, i: (b, 0, after(i))),
    )
    names = ("own", "before", "taps", "bias", "same", *(k for k, _ in more))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=64 * 1024 * 1024)
    return pl.pallas_call(
        functools.partial(kernel, taps=w.shape[0]), grid=(channels // cb, batch, t // tb),
        in_specs=[specs[k] for k in names], out_specs=[specs[k] for k in out_specs], out_shape=out_shape(wt),
        compiler_params=params, interpret=interpret, name=name,  # the custom call's name in the trace
    )(x, x, wt, b.astype(jnp.float32).reshape(channels, 1), same, *(a for _, a in more))


def forward(x, w, b, same, blocks=None, interpret=False):
    """``silu(conv(x) + b)`` float32 (batch, channels, T): ``x`` (batch, channels, T) with T of whole token blocks and
    channels of whole sublane tiles, ``w`` (taps, channels) with ``taps - 1 <= LANES``, ``b`` (channels,), ``same`` from
    ops/document_conv.py::same_document_bits.  ``blocks`` None is ``(TOKEN_BLOCK, CHANNEL_BLOCK)`` (the channels' the
    tallest divisor under it); ``interpret`` runs the kernel in Pallas's interpreter (the CPU tests)."""
    (y,) = _call(_fwd_kernel, x, w, b, same, (), blocks, interpret, name="document_conv_fwd",
                 semantics=("parallel", "parallel", "parallel"), out_specs=["own"],
                 out_shape=lambda wt: [jax.ShapeDtypeStruct(x.shape, jnp.float32)])
    return y


def backward(x, w, b, same, dy, blocks=None, interpret=False):
    """``(dx, dw, db)`` of ``forward`` for the cotangent ``dy`` (batch, channels, T), in the operands' dtypes."""
    # a block of channels walks every token block in order: dw and db stay in VMEM
    dx, dw, db = _call(
        _bwd_kernel, x, w, b, same, (("after", x), ("same_after", same), ("own", dy), ("after", dy)), blocks, interpret,
        name="document_conv_bwd", semantics=("parallel", "arbitrary", "arbitrary"), out_specs=["own", "taps", "bias"],
        out_shape=lambda wt: [jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(wt.shape, jnp.float32),
                              jax.ShapeDtypeStruct((x.shape[1], 1), jnp.float32)])
    return dx, dw[:, :w.shape[0]].T.astype(w.dtype), db[:, 0].astype(b.dtype)
