"""The two passes of ops/attention_edges.py as Pallas TPU kernel pairs: what stands between a
projection's product and the attention kernels, read once and written once each way.

    heads_in    y[h, n, :] = round(scale * rotate(w * x[n, h, :] * rsqrt(mean(x[n, h, :]^2) + eps)))
    heads_out   y[n, h, :] = round(o[h, n, :] * sigmoid(g[n, h, :]))

``x`` and ``g`` are products as the projections give them, TOKEN-major ``(tokens, heads x size)``;
``y`` of ``heads_in`` and ``o`` are HEAD-major ``(heads, tokens, size)``, the layout the splash
kernels take and give.  ``rotate`` is the rotation by halves written with one lane roll,

    rotate(n) = n * [cos, cos] + roll(n, size / 2) * [-sin, sin]

so nothing is split and nothing concatenated; the caller hands both tables ``(tokens, size)``
float32 (they are the same for every head and for q and k), or none (a layer that rotates nothing).

A grid step holds ``TOKEN_BLOCK`` tokens x ``HEAD_BLOCK`` heads: the token-major block
``(tokens, heads x size)`` is thirty-two (eight a step) lane-aligned slices, the head-major block
``(heads, tokens, size)`` the same slices stacked; the heads are the inner axis of the grid, so a
token block's tables are fetched once.  The transposition is therefore nothing but where a slice is
written: no ``copy`` of XLA's, no array in float32 in HBM.

The backward kernels keep nothing of the forward.  ``heads_in``'s reads the cotangent head-major and
the product again and forms, per head, ``g = scale * dy``, the rotation's transpose ``g * [cos, cos] -
roll(g, size / 2) * [-sin, sin]``, then with ``r = rsqrt(mean(x^2) + eps)`` and ``xr = x * r``

    dw += sum over the tokens of g * xr          (float32, one row of partial sums a grid step)
    dx  = r * (g * w - xr * mean(g * w * xr))

and writes ``dx`` token-major.  ``heads_out``'s reads the cotangent, ``o`` and ``g`` and writes
``do = dy * s`` head-major and ``dg = dy * o * s * (1 - s)`` token-major, ``s = sigmoid(g)``.

Precision: every operand converted to float32 first; mean square, ``rsqrt``, the norm's scale, the
tables, the softmax scale and the sigmoid float32, in the order of ``lm_layers.rms_norm`` ->
``rope.apply_rotary_halves`` -> the scale; ONE rounding, at the write, to the operand's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # a head is whole lane tiles

# Tokens and heads (at most) of one grid step.  MEASURED: ops/attention_edges.py.
TOKEN_BLOCK = 512
HEAD_BLOCK = 8


def head_block(heads: int, at_most: int) -> int:
    """The largest divisor of ``heads`` that is no more than ``at_most``."""
    return max(d for d in range(1, heads + 1) if heads % d == 0 and d <= at_most)


def _mean(x):
    return jnp.mean(x, axis=-1, keepdims=True)


def _in_fwd_kernel(x_ref, w_ref, *refs, size, eps, scale):
    *tables, y_ref = refs
    w = w_ref[...]  # (1, size)
    for h in range(y_ref.shape[0]):
        x = x_ref[:, h * size:(h + 1) * size].astype(jnp.float32)  # (tokens, size)
        n = x * jax.lax.rsqrt(_mean(jnp.square(x)) + eps) * w
        if tables:
            cos_ref, sin_ref = tables
            n = n * cos_ref[...] + pltpu.roll(n, size // 2, 1) * sin_ref[...]
        y_ref[h] = (n * scale).astype(y_ref.dtype)


def _in_bwd_kernel(dy_ref, x_ref, w_ref, *refs, size, eps, scale):
    *tables, dx_ref, dw_ref = refs
    w = w_ref[...]
    tokens = x_ref.shape[0]
    dw = jnp.zeros((8, size), jnp.float32)
    for h in range(dy_ref.shape[0]):
        g = dy_ref[h].astype(jnp.float32) * scale
        if tables:
            cos_ref, sin_ref = tables
            g = g * cos_ref[...] - pltpu.roll(g, size // 2, 1) * sin_ref[...]
        x = x_ref[:, h * size:(h + 1) * size].astype(jnp.float32)
        r = jax.lax.rsqrt(_mean(jnp.square(x)) + eps)
        xr = x * r
        dw = dw + jnp.sum((g * xr).reshape(tokens // 8, 8, size), axis=0)  # sublane tiles added up: no reduction across them
        gw = g * w
        dx_ref[:, h * size:(h + 1) * size] = (r * (gw - xr * _mean(gw * xr))).astype(dx_ref.dtype)
    dw_ref[0] = dw


def _out_fwd_kernel(o_ref, g_ref, y_ref, *, size):
    for h in range(o_ref.shape[0]):
        lanes = slice(h * size, (h + 1) * size)
        gate = jax.nn.sigmoid(g_ref[:, lanes].astype(jnp.float32))
        y_ref[:, lanes] = (o_ref[h].astype(jnp.float32) * gate).astype(y_ref.dtype)


def _out_bwd_kernel(dy_ref, o_ref, g_ref, do_ref, dg_ref, *, size):
    for h in range(o_ref.shape[0]):
        lanes = slice(h * size, (h + 1) * size)
        dy = dy_ref[:, lanes].astype(jnp.float32)
        s = jax.nn.sigmoid(g_ref[:, lanes].astype(jnp.float32))
        do_ref[h] = (dy * s).astype(do_ref.dtype)
        dg_ref[:, lanes] = (dy * o_ref[h].astype(jnp.float32) * (s * (1.0 - s))).astype(dg_ref.dtype)


def _call(kernel, operands, outputs, tokens, heads, size, blocks, interpret, name):
    """``kernel`` over the grid (block of tokens, block of heads): ``operands`` and ``outputs`` are
    ``(kind, array or dtype)`` with kind ``tokens`` (tokens, heads x size), ``heads`` (heads, tokens,
    size), ``table`` (tokens, size), ``row`` (1, size) or, an output alone, ``partial`` (one (8, size)
    float32 a grid step)."""
    tb, hb = blocks or (TOKEN_BLOCK, HEAD_BLOCK)
    hb = head_block(heads, hb)
    grid = (tokens // tb, heads // hb)
    specs = dict(
        tokens=pl.BlockSpec((tb, hb * size), lambda i, j: (i, j)),
        heads=pl.BlockSpec((hb, tb, size), lambda i, j: (j, i, 0)),
        table=pl.BlockSpec((tb, size), lambda i, j: (i, 0)),
        row=pl.BlockSpec((1, size), lambda i, j: (0, 0)),
        partial=pl.BlockSpec((1, 8, size), lambda i, j: (i * grid[1] + j, 0, 0)),
    )
    shapes = dict(tokens=(tokens, heads * size), heads=(heads, tokens, size), partial=(grid[0] * grid[1], 8, size))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 1024 * 1024)
    return pl.pallas_call(
        functools.partial(kernel, size=size), grid=grid, in_specs=[specs[k] for k, _ in operands],
        out_specs=[specs[k] for k, _ in outputs], out_shape=[jax.ShapeDtypeStruct(shapes[k], d) for k, d in outputs],
        compiler_params=params, interpret=interpret, name=name,  # the custom call's name in the trace
    )(*(a for _, a in operands))


def _in_operands(x, w, tables):
    row = w.astype(jnp.float32).reshape(1, -1)
    return [("tokens", x), ("row", row), *(("table", t) for t in tables)]


# The four entries are jitted: a step calls each at a few signatures in every layer, forward, recomputed and backward (35
# calls in trinity's), and a jitted callee is traced once a signature and lowered once a step, where a bare ``pallas_call``
# is traced and its Mosaic body serialised at every call (2 s of a warm set-up: PERF.md section 6, PR 48).
@functools.partial(jax.jit, static_argnames=("eps", "scale", "blocks", "interpret"))
def heads_in_fwd(x, w, tables, eps, scale, blocks=None, interpret=False):
    """``x`` (tokens, heads x size) with ``tokens`` whole token blocks and ``size = w.shape[0]`` whole lane
    tiles, ``w`` (size,), ``tables`` ``()`` or the rotation's two float32 ``(tokens, size)``, ``[cos, cos]``
    and ``[-sin, sin]`` -> (heads, tokens, size) in ``x``'s dtype.  ``blocks`` None is ``(TOKEN_BLOCK,
    HEAD_BLOCK)`` (the heads' the largest divisor under it); ``interpret`` runs the kernel in Pallas's
    interpreter (the CPU tests)."""
    size, tokens = w.shape[0], x.shape[0]
    kernel = functools.partial(_in_fwd_kernel, eps=eps, scale=scale)
    (y,) = _call(kernel, _in_operands(x, w, tables), [("heads", x.dtype)], tokens, x.shape[1] // size, size, blocks,
                 interpret, "heads_in_fwd")
    return y


@functools.partial(jax.jit, static_argnames=("eps", "scale", "blocks", "interpret"))
def heads_in_bwd(x, w, tables, dy, eps, scale, blocks=None, interpret=False):
    """``(dx, dw)`` of ``heads_in_fwd`` for the cotangent ``dy`` (heads, tokens, size): ``dx`` in ``x``'s
    dtype, ``dw`` float32."""
    size, tokens = w.shape[0], x.shape[0]
    kernel = functools.partial(_in_bwd_kernel, eps=eps, scale=scale)
    dx, dw = _call(kernel, [("heads", dy), *_in_operands(x, w, tables)], [("tokens", x.dtype), ("partial", jnp.float32)],
                   tokens, x.shape[1] // size, size, blocks, interpret, "heads_in_bwd")
    return dx, jnp.sum(dw, axis=(0, 1))


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def heads_out_fwd(o, g, blocks=None, interpret=False):
    """``o`` (heads, tokens, size), ``g`` (tokens, heads x size) -> ``o * sigmoid(g)`` (tokens, heads x size)
    in ``g``'s dtype."""
    heads, tokens, size = o.shape
    (y,) = _call(_out_fwd_kernel, [("heads", o), ("tokens", g)], [("tokens", g.dtype)], tokens, heads, size, blocks,
                 interpret, "heads_out_fwd")
    return y


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def heads_out_bwd(o, g, dy, blocks=None, interpret=False):
    """``(do, dg)`` of ``heads_out_fwd`` for the cotangent ``dy`` (tokens, heads x size), in the operands'
    dtypes and layouts."""
    heads, tokens, size = o.shape
    return _call(_out_bwd_kernel, [("tokens", dy), ("heads", o), ("tokens", g)], [("heads", o.dtype), ("tokens", g.dtype)],
                 tokens, heads, size, blocks, interpret, "heads_out_bwd")
