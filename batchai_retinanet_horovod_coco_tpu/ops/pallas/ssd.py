"""The chunked state-space scan of ops/ssd.py as a Pallas TPU kernel pair.

Per head (``xdt_j = dt_j X_j``, ``cum`` the inclusive cumulative log-decay
inside a chunk, ``S`` the state a chunk starts from):

    y_i = sum over j <= i, seg_j == seg_i of (C_i . B_j) exp(cum_i - cum_j) xdt_j     (within the chunk)
          + exp(cum_i) (S C_i)                where i is still in the document S belongs to
    S'  = exp(cum_last) S  (if the chunk's last token is still in that document, else 0)
          + sum over j in the last token's document of exp(cum_last - cum_j) xdt_j (x) B_j

The XLA lowering writes mask, decay, ``cb * decay`` and its rounded copy to
HBM for every (chunk, head), keeps every chunk's state there, and moves the
(tokens, heads x head size) operands between the layouts its three einsums
prefer.  Here one grid step holds one (block of heads, chunk); the chunks of a
block run one after another with the state in VMEM, so nothing but x, dt, B,
C in and y out crosses HBM.  The backward kernel walks the chunks in reverse
with the state's cotangent in VMEM, recomputes decays and weights, and reads
the states the forward saved (one (heads x head size, state) float32 block a
chunk).

Groups.  ``B`` and ``C`` come in ``G`` groups of consecutive heads (ops/ssd.py).
A block of heads holds whole groups, and then carries their ``B``, ``C`` and one
``C B^T`` a group (eight groups of eight heads under blocks of 32: four), or lies
inside one group (one group of 64 heads: the block's ``B`` and ``C`` are the
group's, and the blocks' gradients of them are added outside).

Layout.  The tokens are last, (channels, tokens), which is how XLA holds the
mixer's activations around the depthwise convolution: a head is ``head size``
sublanes by ``chunk`` lanes, per-token scalars (dt, decays) are rows, and
every product is a plain, right-transposed or left-transposed matmul.  ``cum``
also comes as columns, for ``cum_i - cum_j``.

Precision, as ops/ssd.py promises: decays, their sums, masks and the carried
state float32; every ``where`` before its ``exp`` (above the diagonal
``cum_i - cum_j`` is positive and unbounded), nothing divided by a decay;
matmul operands rounded to ``x``'s dtype where the XLA lowering rounds them
(``xdt``, the weights, ``xdt`` decayed to the chunk's end, the state a chunk
starts from, and in the backward the cotangents of y and of the state, which
XLA's default-precision matmuls round), accumulation float32.  Where XLA's
transpose rounds a cotangent to bfloat16 only because the primal was bfloat16
(``dw``, the two cotangents of ``xdt`` before they are added), it stays
float32 here, and the cotangent of ``C B^T`` enters its two products in two
bfloat16 pieces: closer to the float32 recurrence, never further.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -jnp.inf
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b

# Heads of one grid step (the grid is batch x blocks of heads x chunks).
#
# MEASURED (v5e-1, PR 29: 64 heads of 64, state 128, T = 8192 in 32 chunks of 256, one
# sequence of 13 documents; ms per call of ops/ssd.py::_chunked with its cumulative sums
# and the layout changes around the kernels; the XLA body 2.547 forward, 4.654 forward +
# backward; and the forward, recomputed forward and backward kernels inside a step of two
# mamba layers, whose whole step the XLA body runs in 109.98 ms):
#   8 heads    forward 0.536, forward + backward 1.948; in the step 0.51 / 0.58 / 1.08, step 95.75
#   16         0.447, 1.743; 0.43 / 0.46 / 1.03, step 95.21
#   32         0.402, 1.589; 0.41 / 0.44 / 0.91, step 94.77  <- taken
#   64         0.389, 1.535 alone (3% under 32); its backward takes Mosaic 15.6 s to compile
#              (32: 5.7 s; every mixer's three calls compile) for 28 MB of blocks in VMEM
#   4          refused: a block of (4, chunk) rows of dt is not whole (8, 128) tiles
# A first pair that held only the within-chunk term (C B^T, chunk states and the product
# across chunks left to XLA's einsums) ran 0.35-0.43 forward and 0.96-1.07 backward a call
# and made the step SLOWER (111.1-113.9 ms): XLA fuses that term into 0.75 + 1.8 ms itself,
# and the layouts its other einsums prefer cost more copies than the kernels saved.
HEADS_PER_BLOCK = 32


def groups_per_block(heads: int, groups: int, hb: int) -> int | None:
    """How many of the ``groups`` of B and C a block of ``hb`` heads carries: whole
    groups, or 1 where the block lies inside a group; nothing where it would cut one."""
    if heads % groups:
        return None
    per_group = heads // groups
    if hb % per_group == 0:
        return hb // per_group
    return 1 if per_group % hb == 0 else None


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


class _Chunk:
    """What both kernels compute of a (block of heads, chunk) before any head:
    the masks and ``C B^T``, transposed: rows are the source token j, the lanes
    the token i it reaches."""

    def __init__(self, bt_ref, ct_ref, seg_row_ref, seg_col_ref, seg_prev_ref, n):
        groups = bt_ref.shape[1] // n  # those this block of heads carries
        self.bt, self.ct = ([ref[0, n * g:n * (g + 1), :] for g in range(groups)] for ref in (bt_ref, ct_ref))
        chunk = bt_ref.shape[-1]
        self.last = slice(chunk - 1, chunk)
        seg_row, seg_col = seg_row_ref[0, 0], seg_col_ref[0, 0]  # (1, l), (l, 1)
        seg_last, seg_prev = seg_row[:, self.last], seg_prev_ref[0, 0][:, self.last]  # (1, 1)
        j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.within = (i >= j) & (seg_col == seg_row)  # (j, i)
        self.reached = seg_row == seg_prev  # (1, l): still the document the carried state belongs to
        self.to_end = seg_row == seg_last  # (1, l): the last token's document
        self.carried = seg_last == seg_prev  # (1, 1)
        self.cbt = [_dot(bt, ct, _TN) for bt, ct in zip(self.bt, self.ct)]  # (j, i) float32

    def head(self, cum_row, cum_col, g):
        """Of a head of the block's group ``g``: (decay, weights) (j, i), and
        the rows ``reach``, ``to_end`` (1, l), ``keep`` (1, 1)."""
        cum_last = cum_row[:, self.last]
        decay = jnp.exp(jnp.where(self.within, cum_row - cum_col, _NEG_INF))  # cum_i - cum_j <= 0 under the mask
        reach = jnp.exp(jnp.where(self.reached, cum_row, _NEG_INF))
        to_end = jnp.exp(jnp.where(self.to_end, cum_last - cum_row, _NEG_INF))
        keep = jnp.where(self.carried, jnp.exp(cum_last), 0.0)
        return decay, self.cbt[g] * decay, reach, to_end, keep


def _fwd_kernel(x_ref, dt_ref, cum_row_ref, cum_col_ref, bt_ref, ct_ref, seg_row_ref, seg_col_ref, seg_prev_ref,
                y_ref, *rest, heads, p, n):
    start_ref, state = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    chunk = _Chunk(bt_ref, ct_ref, seg_row_ref, seg_col_ref, seg_prev_ref, n)
    dtype = x_ref.dtype
    per_group = heads // len(chunk.bt)
    for k in range(heads):
        rows, g = slice(p * k, p * (k + 1)), k // per_group
        _, w, reach, to_end, keep = chunk.head(
            cum_row_ref[0, 0, k:k + 1, :], cum_col_ref[0, 0, 0, :, k:k + 1], g)
        xdt = (x_ref[0, rows, :].astype(jnp.float32) * dt_ref[0, 0, k:k + 1, :]).astype(dtype)  # (p, j)
        s = state[rows, :]  # (p, n): where this chunk starts
        y_ref[0, rows, :] = _dot(xdt, w.astype(dtype)) + _dot(s.astype(dtype), chunk.ct[g]) * reach
        if start_ref is not None:
            start_ref[0, 0, rows, :] = s
        xdt_end = (xdt.astype(jnp.float32) * to_end).astype(dtype)
        state[rows, :] = keep * s + _dot(xdt_end, chunk.bt[g], _NT)


def _bwd_kernel(x_ref, dt_ref, cum_row_ref, cum_col_ref, bt_ref, ct_ref, seg_row_ref, seg_col_ref, seg_prev_ref,
                start_ref, dy_ref, dx_ref, ddt_ref, dcum_row_ref, dcum_col_ref, dbt_ref, dct_ref, dstate, *, heads, p, n):
    """The chunks of a block of heads in reverse; ``dstate`` is the cotangent
    of the state the chunk ends with."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    chunk = _Chunk(bt_ref, ct_ref, seg_row_ref, seg_col_ref, seg_prev_ref, n)
    dtype = x_ref.dtype
    size = bt_ref.shape[-1]
    groups = len(chunk.bt)
    per_group = heads // groups
    last_lane = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    dcum_col = jnp.zeros((size, heads), jnp.float32)
    for k in range(heads):
        rows, group = slice(p * k, p * (k + 1)), k // per_group
        if k % per_group == 0:  # the first head of a group
            dcbt = jnp.zeros((size, size), jnp.float32)
            dbt = jnp.zeros((n, size), jnp.float32)
            dct = jnp.zeros((n, size), jnp.float32)
        dt = dt_ref[0, 0, k:k + 1, :]
        decay, w, reach, to_end, keep = chunk.head(
            cum_row_ref[0, 0, k:k + 1, :], cum_col_ref[0, 0, 0, :, k:k + 1], group)
        x = x_ref[0, rows, :].astype(jnp.float32)
        xdt = (x * dt).astype(dtype)  # (p, j)
        xdt32 = xdt.astype(jnp.float32)
        s = start_ref[0, 0, rows, :]  # (p, n)
        s_op = s.astype(dtype)
        dy = dy_ref[0, rows, :]  # (p, i) float32
        dy_op = dy.astype(dtype)
        # within the chunk: y += xdt @ w
        dw = _dot(xdt, dy_op, _TN)  # (j, i)
        dxdt = _dot(dy_op, w.astype(dtype), _NT)  # (p, j)
        dcbt = dcbt + dw * decay
        g = dw * w  # the cotangent of cum_i - cum_j
        dcum_row = jnp.sum(g, axis=0, keepdims=True)  # to cum_i
        dcum_col = jnp.where(head_lane == k, -jnp.sum(g, axis=1, keepdims=True), dcum_col)  # to cum_j
        # from the state the chunk starts with: y += (s @ C^T) * reach
        dcum_row += jnp.sum(dy * _dot(s_op, chunk.ct[group]), axis=0, keepdims=True) * reach
        dy_reach = (dy * reach).astype(dtype)
        ds = _dot(dy_reach, chunk.ct[group], _NT)  # (p, n)
        dct = dct + _dot(s_op, dy_reach, _TN)
        # the state the chunk ends with: keep * s + (xdt decayed to the end) @ B
        de = dstate[rows, :]
        de_op = de.astype(dtype)
        dxdt_end = _dot(de_op, chunk.bt[group])  # (p, j)
        dbt = dbt + _dot(de_op, (xdt32 * to_end).astype(dtype), _TN)
        dxdt += dxdt_end * to_end
        d_to_end = jnp.sum(dxdt_end * xdt32, axis=0, keepdims=True) * to_end  # to cum_last - cum_j
        dcum_last = jnp.sum(d_to_end, axis=1, keepdims=True) + jnp.sum(de * s, keepdims=True) * keep
        dstate[rows, :] = keep * de + ds
        dcum_row_ref[0, 0, k:k + 1, :] = dcum_row - d_to_end + jnp.where(last_lane, dcum_last, 0.0)
        dx_ref[0, rows, :] = (dxdt * dt).astype(dx_ref.dtype)
        ddt_ref[0, 0, k:k + 1, :] = jnp.sum(dxdt * x, axis=0, keepdims=True)
        if (k + 1) % per_group == 0:  # the last head of a group
            # The cotangent of C B^T in two pieces of the operands' dtype: four small products a group, and
            # B's and C's gradients lie nearer the float32 recurrence than with XLA's one rounding.
            hi = dcbt.astype(dtype)
            lo = (dcbt - hi.astype(jnp.float32)).astype(dtype)
            of_group = slice(n * group, n * (group + 1))
            dbt_ref[0, 0, of_group, :] = dbt + _dot(chunk.ct[group], hi, _NT) + _dot(chunk.ct[group], lo, _NT)
            dct_ref[0, 0, of_group, :] = dct + _dot(chunk.bt[group], hi) + _dot(chunk.bt[group], lo)
    dcum_col_ref[0, 0, 0] = dcum_col


def _operands(x, dt, cum, b, c, seg, hb):
    """The kernels' layouts of ``x`` (batch, chunks, chunk, heads, head size),
    ``dt`` and ``cum`` (batch, chunks, chunk, heads), ``b`` and ``c`` (batch,
    chunks, chunk, [groups,] state), ``seg`` (batch, chunks, chunk)."""
    batch, nc, chunk, heads, p = x.shape
    tokens_last = lambda a: jnp.moveaxis(a.reshape(batch, nc * chunk, -1), 1, 2)  # (batch, channels, tokens)
    rows = lambda a: jnp.moveaxis(a, 3, 2)  # (batch, chunks, heads, chunk)
    cum_col = jnp.moveaxis(cum.reshape(batch, nc, chunk, heads // hb, hb), 3, 2)  # (batch, chunks, blocks, chunk, hb)
    return (tokens_last(x), rows(dt), rows(cum), cum_col, tokens_last(b), tokens_last(c),
            seg[:, :, None, :], seg[..., None])


def _specs(nc, chunk, hb, p, n, gb, blocks_per_group, reverse):
    """Block specs over the grid (batch, block of heads, chunk); ``reverse``
    walks the chunks from the last.  A block carries ``gb`` groups of B and C,
    or ``blocks_per_group`` blocks share one."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    before = lambda c: jnp.maximum(at(c) - 1, 0)
    return dict(
        wide=pl.BlockSpec((1, hb * p, chunk), lambda b, g, c: (b, g, at(c))),  # of (batch, heads x head size, tokens)
        rows=pl.BlockSpec((1, 1, hb, chunk), lambda b, g, c: (b, at(c), g, 0)),  # of (batch, chunks, heads, chunk)
        cols=pl.BlockSpec((1, 1, 1, chunk, hb), lambda b, g, c: (b, at(c), g, 0, 0)),
        # of (batch, groups x state, tokens)
        state_dim=pl.BlockSpec((1, gb * n, chunk), lambda b, g, c: (b, g // blocks_per_group, at(c))),
        seg_row=pl.BlockSpec((1, 1, 1, chunk), lambda b, g, c: (b, at(c), 0, 0)),
        seg_col=pl.BlockSpec((1, 1, chunk, 1), lambda b, g, c: (b, at(c), 0, 0)),
        seg_prev=pl.BlockSpec((1, 1, 1, chunk), lambda b, g, c: (b, before(c), 0, 0)),
        start=pl.BlockSpec((1, 1, hb * p, n), lambda b, g, c: (b, at(c), g, 0)),  # of (batch, chunks, heads x head size, state)
        # of (batch, blocks, groups x state, tokens)
        per_block=pl.BlockSpec((1, 1, gb * n, chunk), lambda b, g, c: (b, g, 0, at(c))),
    )


def _groups(b) -> int:
    return 1 if b.ndim == 4 else b.shape[3]


def _groups_per_block(x, b, hb) -> int:
    gb = groups_per_block(x.shape[3], _groups(b), hb)
    if gb is None:
        raise ValueError(f"a block of {hb} heads cuts a group: {x.shape[3]} heads in {_groups(b)} groups")
    return gb


def _call(kernel, x, dt, cum, b, c, seg, more, hb, interpret, reverse, *, name, out_specs, out_shape):
    """``kernel`` over the grid (batch, block of heads, chunk) on the nine
    operands both kernels read and ``more`` ``(spec name, array)`` pairs;
    ``out_specs`` by name."""
    batch, nc, chunk, heads, p = x.shape
    n, gb = b.shape[-1], _groups_per_block(x, b, hb)
    specs = _specs(nc, chunk, hb, p, n, gb, max(1, heads // _groups(b) // hb), reverse)
    operands = _operands(x, dt, cum, b, c, seg, hb)
    names = ("wide", "rows", "rows", "cols", "state_dim", "state_dim", "seg_row", "seg_col", "seg_prev")
    # A block of heads' chunks run in order: the state is carried in VMEM.
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 * 1024 * 1024)
    return pl.pallas_call(
        functools.partial(kernel, heads=hb, p=p, n=n), grid=(batch, heads // hb, nc),
        in_specs=[specs[k] for k in (*names, *(k for k, _ in more))],
        out_specs=[specs[k] for k in out_specs], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb * p, n), jnp.float32)],
        compiler_params=params, interpret=interpret, name=name,  # the custom call's name in the trace
    )(*operands, operands[6], *(a for _, a in more))


def _forward(x, dt, cum, b, c, seg, hb, interpret, save_start):
    hb = hb or HEADS_PER_BLOCK
    batch, nc, chunk, heads, p = x.shape
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    y, *start = _call(
        _fwd_kernel, x, dt, cum, b, c, seg, (), hb, interpret, False, name="ssd_scan_fwd",
        out_specs=["wide"] + ["start"] * save_start,
        out_shape=[f32(batch, heads * p, nc * chunk)] + [f32(batch, nc, heads * p, b.shape[-1])] * save_start)
    return jnp.moveaxis(y, 1, 2).reshape(x.shape), (start[0] if save_start else None)


def _backward(x, dt, cum, b, c, seg, start, dy, hb, interpret):
    hb = hb or HEADS_PER_BLOCK
    batch, nc, chunk, heads, p = x.shape
    tokens = nc * chunk
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    n, groups, gb = b.shape[-1], _groups(b), _groups_per_block(x, b, hb)
    per_block = f32(batch, heads // hb, gb * n, tokens)
    dy_t = jnp.moveaxis(dy.reshape(batch, tokens, heads * p), 1, 2)
    dx, ddt, dcum_row, dcum_col, dbt, dct = _call(
        _bwd_kernel, x, dt, cum, b, c, seg, (("start", start), ("wide", dy_t)), hb, interpret, True,
        name="ssd_scan_bwd", out_specs=["wide", "rows", "rows", "cols", "per_block", "per_block"],
        out_shape=[jax.ShapeDtypeStruct((batch, heads * p, tokens), x.dtype), f32(batch, nc, heads, chunk),
                   f32(batch, nc, heads, chunk), f32(batch, nc, heads // hb, chunk, hb), per_block, per_block])
    dx = jnp.moveaxis(dx, 1, 2).reshape(x.shape)
    rows_back = lambda a: jnp.moveaxis(a, 2, 3)
    dcum = rows_back(dcum_row) + jnp.moveaxis(dcum_col, 2, 3).reshape(cum.shape)
    # a group's gradient is its blocks' added (one block where a block carries whole groups)
    tokens_first = lambda a: jnp.moveaxis(jnp.sum(a.reshape(batch, groups, -1, n, tokens), axis=2).reshape(
        batch, groups * n, tokens), 1, 2).reshape(b.shape).astype(b.dtype)
    return dx, rows_back(ddt), dcum, tokens_first(dbt), tokens_first(dct)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunked_scan(x, dt, cum, b, c, seg, heads_per_block=None, interpret=False):
    """``x`` (batch, chunks, chunk, heads, head size), ``dt`` and ``cum``
    (batch, chunks, chunk, heads) float32 (``cum`` the inclusive sum of ``dt *
    A`` inside a chunk), ``b`` and ``c`` (batch, chunks, chunk, state) or, in groups
    of consecutive heads, (batch, chunks, chunk, groups, state), ``seg``
    (batch, chunks, chunk) int -> ``Y`` of ops/ssd.py's recurrence, (batch,
    chunks, chunk, heads, head size) float32.  ``heads_per_block`` None is
    ``HEADS_PER_BLOCK``; ``interpret`` runs the kernels in Pallas's
    interpreter (the CPU tests)."""
    return _forward(x, dt, cum, b, c, seg, heads_per_block, interpret, save_start=False)[0]


def _scan_fwd(x, dt, cum, b, c, seg, heads_per_block, interpret):
    y, start = _forward(x, dt, cum, b, c, seg, heads_per_block, interpret, save_start=True)
    return y, (x, dt, cum, b, c, seg, start)


def _scan_bwd(heads_per_block, interpret, residuals, dy):
    return (*_backward(*residuals, dy, heads_per_block, interpret), None)


chunked_scan.defvjp(_scan_fwd, _scan_bwd)
