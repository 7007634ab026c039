"""The chunked gated delta rule of ops/delta_rule.py as a Pallas TPU kernel pair.

Per head (``g`` the inclusive cumulative log-decay inside a chunk of ``C`` tokens,
``S`` (value size, key size) the state the chunk starts from, rows of ``K``, ``V``,
``Q`` the chunk's tokens; masks as ops/delta_rule.py has them):

    D_ij = exp(g_i - g_j)                      i >= j in one document, else 0
    L    = b_i D_ij (k_i . k_j)                i >  j
    T    = (I + L)^-1
    U    = T (b (V - r K S^T))                 r_i = exp(g_i) where i is still in S's document
    O    = r (Q S^T) + (D * Q K^T) U
    S'   = keep S + U^T (e K)                  e_j = exp(g_C - g_j) in the last token's document,
                                               keep = exp(g_C) if that is still S's document

One grid step holds one (block of heads, chunk); the chunks of a block run one
after another with the state in VMEM, so q, k, v and the per-token scalars in
and o out are all that crosses HBM (and, where the backward pass will want them,
one state a chunk).  The backward kernel walks the chunks in reverse with the
state's cotangent in VMEM, forms ``D``, ``L``, ``T`` and ``U`` again and reads
the saved states.

The triangular system is solved EXACTLY, by the recursion over block sizes 1, 2,
4, ..., C/2: with ``T`` the inverse of the diagonal blocks of size s and ``B`` the
part of ``L`` that couples the two halves of each block of 2 s, the inverse of the
blocks of 2 s is ``T - T B T`` (the block form of forward substitution: every
intermediate is the inverse of a diagonal block of ``I + L``, so nothing grows
that the solution itself does not hold; a series in powers of ``L`` cancels
catastrophically where keys repeat and ``b`` nears 2).  Two (C, C, C) products a
level, float32: each operand in two bfloat16 pieces and three passes of the
matrix unit (an error of 2^-16 a product, under every bfloat16 operand beside it);
so is ``U = T (b (V - r K S^T))``, the system's solution: where keys repeat, ``T``'s
entries alternate in sign and ``U`` is a small difference of large terms, and
operands of eight bits of mantissa there moved a state's norm by up to 1%.

Layout.  Tokens last, (channels, tokens), as ops/pallas/ssd.py: a head is 96 or
192 SUBLANES (whole tiles of 8 and 16) by ``C`` lanes, so neither head size has
to be a multiple of 128; the (C, C) matrices are whole lane tiles at C = 128;
per-token scalars come as rows (heads of the block, C) and as columns (C, heads
of the block) for ``g_i - g_j`` and ``b_i``; every product is a plain,
right-transposed or left-transposed matmul.  The 30 heads go in blocks of
``HEADS_PER_BLOCK`` (a divisor: the scalars' blocks are whole arrays' last two
dimensions, so no multiple of 8 is needed).

Precision, as ops/delta_rule.py promises: decays, their sums, masks, ``L``, ``T``,
the system's solution ``U`` and the carried state float32; every ``where`` before its ``exp``; nothing divided
by a decay; the operands of every other product rounded to q's dtype (the state
through ``_state_operand``: the benchmark's STATE control patches that one),
accumulation float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -jnp.inf
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b

# Heads of one grid step (the grid is batch x blocks of heads x chunks): the largest divisor of the heads up to
# this.  MEASURED: the chip readings are kept beside ``lowering`` in ops/delta_rule.py.
HEADS_PER_BLOCK = 6
FWD_NAME, BWD_NAME = "delta_rule_fwd", "delta_rule_bwd"


def heads_per_block(heads: int, most: int = HEADS_PER_BLOCK) -> int:
    """The largest divisor of ``heads`` that is at most ``most``."""
    return max(h for h in range(1, most + 1) if heads % h == 0)


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _state_operand(s, dtype):
    """The carried state as an operand of a product."""
    return s.astype(dtype)


def _dot_split(a, b, dims=_NN):
    """The product of float32 matrices in three bfloat16 passes."""
    a_hi, b_hi = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot(a_hi, b_hi, dims) + (_dot(a_hi, b_lo, dims) + _dot(a_lo, b_hi, dims))


def unit_lower_inverse(lower, split: bool = True):
    """``(I + lower)^-1`` of a strictly lower triangular (C, C) float32 matrix,
    C a power of two: the recursion of the module's docstring.  ``split`` False
    multiplies the float32 matrices as they are (the interpreter on the CPU)."""
    _dot32 = _dot_split if split else _dot
    size = lower.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    t = jnp.where(i == j, 1.0, 0.0) - jnp.where((i ^ j) == 1, lower, 0.0)
    level = 1
    while (1 << level) < size:
        coupling = jnp.where(((i >> level) ^ (j >> level)) == 1, lower, 0.0)
        t = t - _dot32(t, _dot32(coupling, t))
        level += 1
    return t


class _Chunk:
    """What both kernels compute of a (block of heads, chunk) before any head:
    the masks; rows are the token i, lanes the earlier token j."""

    def __init__(self, seg_row_ref, seg_col_ref, seg_prev_ref):
        seg_row, seg_col = seg_row_ref[0, 0], seg_col_ref[0, 0]  # (1, C), (C, 1)
        size = seg_row.shape[-1]
        self.last = slice(size - 1, size)
        seg_last, seg_prev = seg_row[:, self.last], seg_prev_ref[0, 0][:, self.last]  # (1, 1)
        i = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.within = (i >= j) & (seg_col == seg_row)
        self.strict = i > j
        # (the first chunk's ``seg_prev`` is its own row: it starts from a state of zeros, which reaches nothing)
        self.reached = seg_row == seg_prev  # (1, C): still the document the carried state belongs to
        self.to_end = seg_row == seg_last  # (1, C): the last token's document
        self.carried = seg_last == seg_prev  # (1, 1)

    def head(self, kt, g_row, g_col, b_col):
        """Of one head: ``D``, ``K K^T``, ``L`` (i, j), and the rows ``r``, ``e``
        (1, C) and ``keep`` (1, 1)."""
        g_last = g_row[:, self.last]
        decay = jnp.exp(jnp.where(self.within, g_col - g_row, _NEG_INF))  # g_i - g_j <= 0 under the mask
        kk = _dot(kt, kt, _TN)
        lower = jnp.where(self.strict, b_col * decay * kk, 0.0)
        r = jnp.exp(jnp.where(self.reached, g_row, _NEG_INF))
        e = jnp.exp(jnp.where(self.to_end, g_last - g_row, _NEG_INF))
        keep = jnp.where(self.carried, jnp.exp(g_last), 0.0)
        return decay, kk, lower, r, e, keep


def _fwd_kernel(q_ref, k_ref, v_ref, g_row_ref, g_col_ref, b_row_ref, b_col_ref, seg_row_ref, seg_col_ref,
                seg_prev_ref, o_ref, sq_ref, *rest, heads, dk, dv, split):
    start_ref, state = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    chunk = _Chunk(seg_row_ref, seg_col_ref, seg_prev_ref)
    dtype = q_ref.dtype
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    sq = jnp.zeros((1, heads), jnp.float32)
    for h in range(heads):
        of_k, of_v = slice(dk * h, dk * (h + 1)), slice(dv * h, dv * (h + 1))
        qt, kt, vt = q_ref[0, of_k, :], k_ref[0, of_k, :], v_ref[0, of_v, :]
        g_row, b_row = g_row_ref[0, 0, 0, h:h + 1, :], b_row_ref[0, 0, 0, h:h + 1, :]
        decay, _, lower, r, e, keep = chunk.head(
            kt, g_row, g_col_ref[0, 0, 0, :, h:h + 1], b_col_ref[0, 0, 0, :, h:h + 1])
        t = unit_lower_inverse(lower, split)
        s = state[of_v, :]  # (dv, dk): where this chunk starts
        s_op = _state_operand(s, dtype)
        rt = b_row * (vt.astype(jnp.float32) - r * _dot(s_op, kt))
        ut = (_dot_split if split else _dot)(rt, t, _NT).astype(dtype)  # (dv, C): the system's solution, float32
        weights = (_dot(qt, kt, _TN) * decay).astype(dtype)  # (i, j)
        o_ref[0, of_v, :] = r * _dot(s_op, qt) + _dot(ut, weights, _NT)
        if start_ref is not None:
            start_ref[0, 0, of_v, :] = s
        s = keep * s + _dot(ut, (kt.astype(jnp.float32) * e).astype(dtype), _NT)
        state[of_v, :] = s
        sq = jnp.where(head_lane == h, jnp.sum(s * s, keepdims=True), sq)
    sq_ref[0, 0, 0] = sq


def _bwd_kernel(q_ref, k_ref, v_ref, g_row_ref, g_col_ref, b_row_ref, b_col_ref, seg_row_ref, seg_col_ref,
                seg_prev_ref, start_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_row_ref, dg_col_ref, db_row_ref, db_col_ref,
                dstate, *, heads, dk, dv, split):
    """The chunks of a block of heads in reverse; ``dstate`` is the cotangent
    of the state the chunk ends with."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    chunk = _Chunk(seg_row_ref, seg_col_ref, seg_prev_ref)
    dtype = q_ref.dtype
    size = seg_row_ref.shape[-1]
    last_lane = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    dg_col = jnp.zeros((size, heads), jnp.float32)
    db_col = jnp.zeros((size, heads), jnp.float32)
    rows = lambda x: jnp.sum(x, axis=0, keepdims=True)  # over the channels: one number a token
    for h in range(heads):
        of_k, of_v = slice(dk * h, dk * (h + 1)), slice(dv * h, dv * (h + 1))
        qt, kt, vt = q_ref[0, of_k, :], k_ref[0, of_k, :], v_ref[0, of_v, :]
        g_row, b_row = g_row_ref[0, 0, 0, h:h + 1, :], b_row_ref[0, 0, 0, h:h + 1, :]
        b_col = b_col_ref[0, 0, 0, :, h:h + 1]
        decay, kk, lower, r, e, keep = chunk.head(kt, g_row, g_col_ref[0, 0, 0, :, h:h + 1], b_col)
        # the forward pass again
        t = unit_lower_inverse(lower, split)
        t_op = t.astype(dtype)
        s = start_ref[0, 0, of_v, :]
        s_op = _state_operand(s, dtype)
        pt = _dot(s_op, kt)  # (dv, C)
        wt = vt.astype(jnp.float32) - r * pt
        ut = (_dot_split if split else _dot)(b_row * wt, t, _NT).astype(dtype)
        qk = _dot(qt, kt, _TN)  # (i, j)
        weights = qk * decay
        sq = _dot(s_op, qt)  # (dv, C)
        ke = (kt.astype(jnp.float32) * e).astype(dtype)
        # backward
        do = do_ref[0, of_v, :]  # (dv, C) float32
        do_op = do.astype(dtype)
        ds1 = dstate[of_v, :]  # (dv, dk)
        ds1_op = ds1.astype(dtype)
        dut = _dot(ds1_op, ke) + _dot(do_op, weights.astype(dtype))  # (dv, C)
        dke = _dot(ds1_op, ut, _TN)  # (dk, C): of e K
        de = rows(dke * kt.astype(jnp.float32))
        dkeep = jnp.sum(ds1 * s, keepdims=True)
        do_r = (do * r).astype(dtype)
        dr = rows(do * sq)
        dweights = _dot(do_op, ut, _TN)  # (i, j)
        dqk = (dweights * decay).astype(dtype)
        drt = _dot(dut.astype(dtype), t_op)  # (dv, C)
        drt_op = drt.astype(dtype)
        dlower = jnp.where(chunk.strict, -_dot(drt_op, ut, _TN), 0.0)  # (i, j)
        dpt = (-(b_row * r) * drt).astype(dtype)
        dr = dr - b_row * rows(drt * pt)
        dkk = (dlower * b_col * decay).astype(dtype)
        dkt = (dke * e + _dot(qt, dqk) + _dot(s_op, dpt, _TN) + _dot(kt, dkk) + _dot(kt, dkk, _NT))
        dqt = _dot(s_op, do_r, _TN) + _dot(kt, dqk, _NT)
        dstate[of_v, :] = keep * ds1 + _dot(do_r, qt, _NT) + _dot(dpt, kt, _NT)
        through_decay = dweights * weights + dlower * lower  # the cotangent of g_i - g_j
        de_e = de * e
        dg_last = jnp.sum(de_e, axis=1, keepdims=True) + dkeep * keep
        dq_ref[0, of_k, :] = dqt.astype(dq_ref.dtype)
        dk_ref[0, of_k, :] = dkt.astype(dk_ref.dtype)
        dv_ref[0, of_v, :] = (b_row * drt).astype(dv_ref.dtype)
        dg_row_ref[0, 0, 0, h:h + 1, :] = (-rows(through_decay) + dr * r - de_e + jnp.where(last_lane, dg_last, 0.0))
        db_row_ref[0, 0, 0, h:h + 1, :] = rows(drt * wt)
        dg_col = jnp.where(head_lane == h, jnp.sum(through_decay, axis=1, keepdims=True), dg_col)
        db_col = jnp.where(head_lane == h, jnp.sum(dlower * decay * kk, axis=1, keepdims=True), db_col)
    dg_col_ref[0, 0, 0] = dg_col
    db_col_ref[0, 0, 0] = db_col


def _operands(q, k, v, g, b, seg, hb):
    """The kernels' layouts of ``q``, ``k`` (batch, T, heads, key size), ``v``
    (batch, T, heads, value size), ``g`` and ``b`` (batch, chunks, C, heads),
    ``seg`` (batch, chunks, C)."""
    batch, nc, size, heads = g.shape
    tokens_last = lambda a: jnp.moveaxis(a.reshape(batch, nc * size, -1), 1, 2)  # (batch, channels, tokens)
    blocks = lambda a: a.reshape(batch, nc, size, heads // hb, hb)
    rows = lambda a: jnp.transpose(blocks(a), (0, 1, 3, 4, 2))  # (batch, chunks, blocks, hb, C)
    cols = lambda a: jnp.moveaxis(blocks(a), 2, 3)  # (batch, chunks, blocks, C, hb)
    return (tokens_last(q), tokens_last(k), tokens_last(v), rows(g), cols(g), rows(b), cols(b),
            seg[:, :, None, :], seg[..., None])


def _specs(nc, size, hb, dk, dv, reverse):
    """Block specs over the grid (batch, block of heads, chunk); ``reverse``
    walks the chunks from the last."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    before = lambda c: jnp.maximum(at(c) - 1, 0)
    return dict(
        keys=pl.BlockSpec((1, hb * dk, size), lambda b, g, c: (b, g, at(c))),  # of (batch, heads x key size, tokens)
        values=pl.BlockSpec((1, hb * dv, size), lambda b, g, c: (b, g, at(c))),
        rows=pl.BlockSpec((1, 1, 1, hb, size), lambda b, g, c: (b, at(c), g, 0, 0)),
        cols=pl.BlockSpec((1, 1, 1, size, hb), lambda b, g, c: (b, at(c), g, 0, 0)),
        seg_row=pl.BlockSpec((1, 1, 1, size), lambda b, g, c: (b, at(c), 0, 0)),
        seg_col=pl.BlockSpec((1, 1, size, 1), lambda b, g, c: (b, at(c), 0, 0)),
        seg_prev=pl.BlockSpec((1, 1, 1, size), lambda b, g, c: (b, before(c), 0, 0)),
        start=pl.BlockSpec((1, 1, hb * dv, dk), lambda b, g, c: (b, at(c), g, 0)),  # of (batch, chunks, heads x value size, key size)
        norms=pl.BlockSpec((1, 1, 1, 1, hb), lambda b, g, c: (b, at(c), g, 0, 0)),  # of (batch, chunks, blocks, 1, hb)
    )


def _call(kernel, q, k, v, g, b, seg, more, hb, interpret, reverse, *, name, out_specs, out_shape):
    """``kernel`` over the grid (batch, block of heads, chunk) on the ten
    operands both kernels read and ``more`` ``(spec name, array)`` pairs;
    ``out_specs`` by name."""
    batch, nc, size, heads = g.shape
    dk, dv = q.shape[-1], v.shape[-1]
    specs = _specs(nc, size, hb, dk, dv, reverse)
    operands = _operands(q, k, v, g, b, seg, hb)
    names = ("keys", "keys", "values", "rows", "cols", "rows", "cols", "seg_row", "seg_col", "seg_prev")
    # A block of heads' chunks run in order: the state is carried in VMEM.
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 * 1024 * 1024)
    return pl.pallas_call(
        functools.partial(kernel, heads=hb, dk=dk, dv=dv, split=not interpret), grid=(batch, heads // hb, nc),
        in_specs=[specs[n] for n in (*names, *(n for n, _ in more))],
        out_specs=[specs[n] for n in out_specs], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb * dv, dk), jnp.float32)],
        compiler_params=params, interpret=interpret, name=name,  # the custom call's name in the trace
    )(*operands, operands[7], *(a for _, a in more))


def _forward(q, k, v, g, b, seg, hb, interpret, save_start):
    batch, nc, size, heads = g.shape
    hb = hb or heads_per_block(heads)
    dk, dv = q.shape[-1], v.shape[-1]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    o, sq, *start = _call(
        _fwd_kernel, q, k, v, g, b, seg, (), hb, interpret, False, name=FWD_NAME,
        out_specs=["values", "norms"] + ["start"] * save_start,
        out_shape=[f32(batch, heads * dv, nc * size), f32(batch, nc, heads // hb, 1, hb)]
        + [f32(batch, nc, heads * dv, dk)] * save_start)
    o = jnp.moveaxis(o, 1, 2).reshape(v.shape)
    return (o, sq.reshape(batch, nc, heads)), (start[0] if save_start else None)


def _backward(q, k, v, g, b, seg, start, do, hb, interpret):
    batch, nc, size, heads = g.shape
    hb = hb or heads_per_block(heads)
    dk, dv = q.shape[-1], v.shape[-1]
    tokens = nc * size
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    like = lambda a, d: jax.ShapeDtypeStruct((batch, heads * d, tokens), a.dtype)
    rows, cols = f32(batch, nc, heads // hb, hb, size), f32(batch, nc, heads // hb, size, hb)
    do_t = jnp.moveaxis(do.reshape(batch, tokens, heads * dv), 1, 2)
    dq, dk_, dv_, dg_row, dg_col, db_row, db_col = _call(
        _bwd_kernel, q, k, v, g, b, seg, (("start", start), ("values", do_t)), hb, interpret, True, name=BWD_NAME,
        out_specs=["keys", "keys", "values", "rows", "cols", "rows", "cols"],
        out_shape=[like(q, dk), like(k, dk), like(v, dv), rows, cols, rows, cols])
    tokens_first = lambda a, like_: jnp.moveaxis(a, 1, 2).reshape(like_.shape)
    both = lambda row, col: (jnp.transpose(row, (0, 1, 4, 2, 3)) + jnp.moveaxis(col, 3, 2)).reshape(g.shape)
    return (tokens_first(dq, q), tokens_first(dk_, k), tokens_first(dv_, v), both(dg_row, dg_col),
            both(db_row, db_col))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunked_delta_rule(q, k, v, g, b, seg, heads_per_block_=None, interpret=False):
    """``q``, ``k`` (batch, T, heads, key size) and ``v`` (batch, T, heads, value
    size) in one dtype, ``g`` (the inclusive sum of the log-decays inside a chunk)
    and ``b`` (batch, chunks, C, heads) float32, ``seg`` (batch, chunks, C) int,
    T = chunks x C -> ``(o, sq)``: the outputs of ops/delta_rule.py's
    recurrence (batch, T, heads, value size) float32 and the squared Frobenius
    norm of the state every chunk ends with, (batch, chunks, heads) (no
    gradient flows through it).  ``heads_per_block_`` None is the largest
    divisor of the heads up to ``HEADS_PER_BLOCK``; ``interpret`` runs the
    kernels in Pallas's interpreter (the CPU tests)."""
    return _forward(q, k, v, g, b, seg, heads_per_block_, interpret, save_start=False)[0]


def _rule_fwd(q, k, v, g, b, seg, heads_per_block_, interpret):
    out, start = _forward(q, k, v, g, b, seg, heads_per_block_, interpret, save_start=True)
    return out, (q, k, v, g, b, seg, start)


def _rule_bwd(heads_per_block_, interpret, residuals, cotangents):
    do, _ = cotangents  # the norms carry no gradient
    return (*_backward(*residuals, do, heads_per_block_, interpret), None)


chunked_delta_rule.defvjp(_rule_fwd, _rule_bwd)
