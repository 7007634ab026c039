"""The TPU kernels of the learned sparse attention (ops/sparse_attention.py),
each for ONE sequence of ``T`` tokens, the first three for a run of ``rows``
queries that starts at ``row0`` (``T``, ``rows`` and ``row0`` whole tiles):

- ``index_scores``: ``I[t, s] = scale * sum_j w[t, j] relu(qI[t, j] . kI[s])``.
  A grid step holds one (query tile, key tile): the heads' products go through
  the MXU one after another and ReLU, weight and sum happen on the tile in
  VMEM, so the (heads, rows, T) float32 products XLA would write and read back
  never exist.  Tiles above the diagonal (no key of theirs is at or before a
  query of theirs) are written as zeros and cost no product.
  ``index_scores_bwd``: a dq kernel (with the weights' gradient) and a dk
  kernel in the same tiles, each forming a tile's head products again.
- ``thresholds``: per query the ``topk``-th largest score among the keys allowed
  (``s <= t`` of ``t``'s document) as an ordered int32 pattern, and where its ties
  are cut.  A grid step holds a tile of queries with ALL their scores in VMEM:
  the patterns are formed once, then 32 counting passes decide the threshold's
  bits (ops/sparse_attention.py::_kth_largest) without touching HBM again; only
  the columns up to the tile's last query are visited.  Where more keys tie
  with the threshold than there is room for, as many passes again as a
  position has bits find the position of the last tie taken.
- ``mean_probs``: ``P[t, s] = mean over heads a of exp(q[t, a] . k[s, g(a)] - lse[t, a])``
  on the selected pairs, zero elsewhere: the main attention's probabilities,
  averaged, from the log-sum-exp its kernel saved.  A grid step holds the
  query heads of one key-value head; the key-value heads are the grid's
  innermost axis and the tile of ``P`` stays in VMEM while they add up.
- ``masked_attention`` (forward, and ``masked_attention_bwd``: a dq and a dk/dv
  kernel): blocked softmax attention with a running maximum and sum under a
  mask that is DATA, one int8 (T, T) array shared by all heads.  A grid step
  holds ALL the query heads of one key-value head as rows of one matrix, so a
  tile of keys, values and mask is fetched once a group and the heads'
  gradients of a key add up inside the product.  The blocks on or under the
  diagonal run, all of them, whatever the mask holds; those above it are left
  out and fetch nothing.  The kernels take no scale (``q`` comes scaled).

Precision of the attention kernels: operands in the caller's dtype (bfloat16),
scores, maximum, sum, accumulators float32; the probabilities are rounded to
the operands' dtype for their products (forward and backward), ``dS`` too.  A
masked score is a large finite negative: every query has a selected key, so a
row's garbage before its first selected key is wiped when that key arrives
(the rescaling factor is exactly 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
INT32_MIN = np.int32(-(2 ** 31))
MASKED = -1e30

SCORE_TILE = 512  # queries and keys of one step of ``index_scores``
PROBS_TILE = (256, 512)  # queries and keys of one step of ``mean_probs``
QUERY_TILE = 64  # queries of one step of ``thresholds`` (their T scores: 4 MB at T = 16 384)
COLUMNS = 2048  # keys a counting pass reads at a time
ATTENTION_TILE = (256, 512)  # queries (of every head of the group) and keys of one step of the attention kernels
VMEM_LIMIT = 64 * 1024 * 1024


def _tile(t: int, most: int) -> int:
    """The largest tile up to ``most`` that divides ``t`` (``t`` itself if smaller)."""
    return t if t <= most else max(b for b in range(128, most + 1, 128) if t % b == 0)


# ---- index scores --------------------------------------------------------------


def _scores_kernel(q_ref, w_ref, k_ref, o_ref, *, scale, heads, bq, bk, row0):
    i, j = pl.program_id(0), pl.program_id(1)
    runs = j * bk <= row0 + i * bq + bq - 1

    @pl.when(runs)
    def _():
        k = k_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            z = jax.lax.dot_general(q_ref[h], k, _NT, preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(z, 0.0)
        o_ref[...] = acc * scale

    @pl.when(jnp.logical_not(runs))
    def _():
        o_ref[...] = jnp.zeros((bq, bk), jnp.float32)


def index_scores(q_idx, k_idx, w, scale: float, row0: int = 0, interpret: bool = False, tile: int | None = None):
    """``q_idx`` (rows, heads, size) and ``w`` (rows, heads) of the queries from
    ``row0`` on, ``k_idx`` (T, size) -> (rows, T) float32."""
    rows, heads, size = q_idx.shape
    t = k_idx.shape[0]
    bq = bk = tile or _tile(rows, SCORE_TILE)
    last_needed = lambda i, j: jnp.minimum(j, (row0 + i * bq + bq - 1) // bk)  # a tile left out fetches nothing new
    return pl.pallas_call(
        functools.partial(_scores_kernel, scale=scale, heads=heads, bq=bq, bk=bk, row0=row0),
        grid=(rows // bq, t // bk),
        in_specs=[pl.BlockSpec((heads, bq, size), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((bq, heads), lambda i, j: (i, 0)),
                  pl.BlockSpec((bk, size), lambda i, j: (last_needed(i, j), 0))],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name="dsa_index_scores", interpret=interpret,
    )(q_idx.transpose(1, 0, 2), w.astype(jnp.float32), k_idx)


def _head_gradient(q_ref, w_ref, k, g, h):
    """One head's part of a step of the scores' backward pass: ``(relu(z), dz)``,
    both (bq, bk) float32, ``z`` the head's products formed again."""
    z = jax.lax.dot_general(q_ref[h], k, _NT, preferred_element_type=jnp.float32)
    return jnp.maximum(z, 0.0), jnp.where(z > 0.0, g * w_ref[:, h:h + 1], 0.0)


def _scores_dq_kernel(q_ref, w_ref, k_ref, d_ref, dq_ref, dw_ref, dq_acc, dw_acc, *, scale, heads, bq, bk, row0, nk):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, jnp.float32)

    @pl.when(j * bk <= row0 + i * bq + bq - 1)
    def _():
        k, g = k_ref[...], d_ref[...] * scale
        for h in range(heads):
            relu_z, dz = _head_gradient(q_ref, w_ref, k, g, h)
            dw_acc[h] += jnp.sum(g * relu_z, axis=1, keepdims=True)
            dq_acc[h] += jnp.dot(dz.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_acc[...]


def _scores_dk_kernel(q_ref, w_ref, k_ref, d_ref, dk_ref, dk_acc, *, scale, heads, bq, bk, row0, nq):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)

    @pl.when(j * bk <= row0 + i * bq + bq - 1)
    def _():
        k, g = k_ref[...], d_ref[...] * scale
        for h in range(heads):
            _, dz = _head_gradient(q_ref, w_ref, k, g, h)
            dk_acc[...] += jax.lax.dot_general(dz.astype(k.dtype), q_ref[h], _TN, preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)


def index_scores_bwd(q_idx, k_idx, w, d_scores, scale: float, row0: int = 0, interpret: bool = False,
                     tile: int | None = None):
    """The gradients of ``index_scores`` for its ``q_idx``, ``k_idx`` and ``w``
    from ``d_scores`` (rows, T) float32 (zero wherever nothing was selected): a
    dq kernel (with the weights' gradient) and a dk kernel, each forming a tile's
    head products again; ``dS`` is rounded to the operands' dtype for its
    products, as the attention kernels' is."""
    rows, heads, size = q_idx.shape
    t = k_idx.shape[0]
    bq = bk = tile or _tile(rows, SCORE_TILE)
    nq, nk = rows // bq, t // bk
    operands = (q_idx.transpose(1, 0, 2), w.astype(jnp.float32), k_idx, d_scores)
    last_needed = lambda i, j: jnp.minimum(j, (row0 + i * bq + bq - 1) // bk)
    dq, dw = pl.pallas_call(
        functools.partial(_scores_dq_kernel, scale=scale, heads=heads, bq=bq, bk=bk, row0=row0, nk=nk),
        grid=(nq, nk),
        in_specs=[pl.BlockSpec((heads, bq, size), lambda i, j: (0, i, 0)), pl.BlockSpec((bq, heads), lambda i, j: (i, 0)),
                  pl.BlockSpec((bk, size), lambda i, j: (last_needed(i, j), 0)),
                  pl.BlockSpec((bq, bk), lambda i, j: (i, last_needed(i, j)))],
        out_specs=[pl.BlockSpec((heads, bq, size), lambda i, j: (0, i, 0)),
                   pl.BlockSpec((heads, bq, 1), lambda i, j: (0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((heads, rows, size), q_idx.dtype),
                   jax.ShapeDtypeStruct((heads, rows, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, bq, size), jnp.float32), pltpu.VMEM((heads, bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT),
        name="dsa_index_scores_dq", interpret=interpret,
    )(*operands)
    # a key tile's first query tile: the one that holds the key tile's first position, or the run's first
    first_needed = lambda j, i: jnp.maximum(i, jnp.maximum(j * bk - row0, 0) // bq)
    seen = min(t, row0 + rows)  # no query of the run sees a later key: their gradient is zero
    dk = pl.pallas_call(
        functools.partial(_scores_dk_kernel, scale=scale, heads=heads, bq=bq, bk=bk, row0=row0, nq=nq),
        grid=(seen // bk, nq),
        in_specs=[pl.BlockSpec((heads, bq, size), lambda j, i: (0, first_needed(j, i), 0)),
                  pl.BlockSpec((bq, heads), lambda j, i: (first_needed(j, i), 0)),
                  pl.BlockSpec((bk, size), lambda j, i: (j, 0)),
                  pl.BlockSpec((bq, bk), lambda j, i: (first_needed(j, i), j))],
        out_specs=pl.BlockSpec((bk, size), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((seen, size), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, size), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT),
        name="dsa_index_scores_dk", interpret=interpret,
    )(*operands)
    dk = jnp.pad(dk, [(0, t - seen), (0, 0)])
    return dq.transpose(1, 0, 2), dk, dw[..., 0].T


# ---- thresholds ------------------------------------------------------------------


def _thresholds_kernel(s_ref, seg_q_ref, seg_k_ref, tau_ref, cut_ref, tied_ref, keys_ref, *, topk, bq, t, columns,
                       position_bits, row0):
    i = pl.program_id(0)
    pos_q = row0 + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    seg_q = seg_q_ref[...]
    chunks = (jnp.minimum(row0 + i * bq + bq, t) + columns - 1) // columns  # the columns up to the tile's last query

    def columns_of(c):
        return pl.ds(pl.multiple_of(c * columns, columns), columns)

    def allowed_of(c):
        pos_k = c * columns + jax.lax.broadcasted_iota(jnp.int32, (1, columns), 1)
        return (pos_k <= pos_q) & (seg_k_ref[:, columns_of(c)] == seg_q), pos_k

    def form(c, _):
        scores = s_ref[:, columns_of(c)]
        bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
        keys = jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)
        keys_ref[:, columns_of(c)] = jnp.where(allowed_of(c)[0], jnp.where(scores == 0.0, 0, keys), INT32_MIN)
        return 0

    jax.lax.fori_loop(0, chunks, form, 0)

    def count(test):
        """Per query, over the visited columns, how many keys pass ``test(keys, chunk)``."""
        def add(c, acc):
            return acc + jnp.sum(test(keys_ref[:, columns_of(c)], c).astype(jnp.int32), axis=1, keepdims=True)
        return jax.lax.fori_loop(0, chunks, add, jnp.zeros((bq, 1), jnp.int32))

    def decide(b, pattern):
        candidate = pattern | jax.lax.shift_left(jnp.int32(1), 31 - b)
        floor = candidate ^ INT32_MIN
        return jnp.where(count(lambda keys, c: keys >= floor) >= topk, candidate, pattern)

    tau = jax.lax.fori_loop(0, 32, decide, jnp.zeros((bq, 1), jnp.int32)) ^ INT32_MIN
    is_tie = lambda keys, c: (keys == tau) & allowed_of(c)[0]
    room = topk - count(lambda keys, c: keys > tau)
    tied = count(is_tie) > room
    tau_ref[...] = tau
    tied_ref[...] = tied.astype(jnp.int32)
    cut_ref[...] = jnp.full((bq, 1), t, jnp.int32)

    @pl.when(jnp.max(tied.astype(jnp.int32)) > 0)
    def _():
        # the largest p of which fewer than ``room`` ties lie before p: the position of the room-th tie
        def decide_position(b, p):
            candidate = p | jax.lax.shift_left(jnp.int32(1), position_bits - 1 - b)
            before = count(lambda keys, c: is_tie(keys, c) & (allowed_of(c)[1] < candidate))
            return jnp.where(before < room, candidate, p)

        p = jax.lax.fori_loop(0, position_bits, decide_position, jnp.zeros((bq, 1), jnp.int32))
        cut_ref[...] = jnp.minimum(p, t)


def thresholds(scores, segment_ids, topk: int, row0: int = 0, interpret: bool = False, query_tile: int | None = None,
               columns: int | None = None):
    """``scores`` (rows, T) float32 of the queries from ``row0`` on, ``segment_ids``
    (T,) -> ``(tau, cut, tied)``, each (rows,): int32, int32, bool
    (ops/sparse_attention.py::Thresholds)."""
    rows, t = scores.shape
    bq, columns = query_tile or min(QUERY_TILE, rows), columns or _tile(t, COLUMNS)
    assert rows % bq == 0 and t % columns == 0
    column = pl.BlockSpec((bq, 1), lambda i: (i, 0))
    seg = segment_ids.astype(jnp.int32)
    tau, cut, tied = pl.pallas_call(
        functools.partial(_thresholds_kernel, topk=topk, bq=bq, t=t, columns=columns,
                          position_bits=int(t).bit_length(), row0=row0),
        grid=(rows // bq,),
        in_specs=[pl.BlockSpec((bq, t), lambda i: (i, 0)), column, pl.BlockSpec((1, t), lambda i: (0, 0))],
        out_specs=[column, column, column],
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32)] * 3,
        scratch_shapes=[pltpu.VMEM((bq, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        name="dsa_thresholds", interpret=interpret,
    )(scores, seg[row0:row0 + rows, None], seg[None, :])
    return tau[:, 0], cut[:, 0], tied[:, 0] > 0


# ---- what the attention kernels share ------------------------------------------------


def _grouped(x, kv_heads: int):
    """(heads, T, ...) -> (kv_heads, group, T, ...): the query heads of a key-value head side by side."""
    return x.reshape(kv_heads, x.shape[0] // kv_heads, *x.shape[1:])


def _group_scores(q_ref, k_ref, mask_ref, group, bq, bk):
    """The scores of one step, (group x bq, bk) float32, masked, and the rows' matrix of ``q``."""
    q = q_ref[...].reshape(group * bq, q_ref.shape[-1])
    s = jax.lax.dot_general(q, k_ref[...], _NT, preferred_element_type=jnp.float32)
    keep = mask_ref[...].astype(jnp.int32) != 0
    return jnp.where(keep[None], s.reshape(group, bq, bk), MASKED).reshape(group * bq, bk), q


# ---- the mean of the heads' probabilities -----------------------------------------


def _probs_kernel(q_ref, k_ref, lse_ref, mask_ref, o_ref, *, kv_heads, group, bq, bk, row0):
    i, j, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    runs = j * bk <= row0 + i * bq + bq - 1

    @pl.when(g == 0)
    def _():
        o_ref[...] = jnp.zeros((bq, bk), jnp.float32)

    @pl.when(runs)
    def _():
        s, _ = _group_scores(q_ref, k_ref, mask_ref, group, bq, bk)
        p = jnp.exp(s - lse_ref[...].reshape(group * bq, 1))  # a masked score is MASKED: its probability 0
        o_ref[...] += jnp.sum(p.reshape(group, bq, bk), axis=0) * (1.0 / (group * kv_heads))


def mean_probs(q, k, lse, mask, row0: int = 0, interpret: bool = False, tiles: tuple[int, int] | None = None):
    """``q`` (heads, rows, size) scaled and ``lse`` (heads, rows) float32 of the
    queries from ``row0`` on, ``k`` (kv_heads, T, size), ``mask`` (rows, T) int8
    -> (rows, T) float32."""
    heads, rows, size = q.shape
    kv_heads, t = k.shape[:2]
    group = heads // kv_heads
    bq, bk = tiles or (_tile(rows, PROBS_TILE[0]), _tile(t, PROBS_TILE[1]))
    runs = lambda i, j: j * bk <= row0 + i * bq + bq - 1
    return pl.pallas_call(
        functools.partial(_probs_kernel, kv_heads=kv_heads, group=group, bq=bq, bk=bk, row0=row0),
        grid=(rows // bq, t // bk, kv_heads),
        in_specs=[  # a tile above the diagonal asks for one block all along, so fetches it once
            pl.BlockSpec((None, group, bq, size), lambda i, j, g: (jnp.where(runs(i, j), g, 0), 0, i, 0)),
            pl.BlockSpec((None, bk, size), lambda i, j, g: (jnp.where(runs(i, j), g, 0), jnp.where(runs(i, j), j, 0), 0)),
            pl.BlockSpec((None, group, bq, 1), lambda i, j, g: (jnp.where(runs(i, j), g, 0), 0, i, 0)),
            pl.BlockSpec((bq, bk), lambda i, j, g: (i, jnp.where(runs(i, j), j, 0)))],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j, g: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        name="dsa_mean_probs", interpret=interpret,
    )(_grouped(q, kv_heads), k, _grouped(lse[..., None], kv_heads), mask)


# ---- the attention under a mask that is data ------------------------------------------


def _attention_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, group, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _():
        s, _ = _group_scores(q_ref, k_ref, mask_ref, group, bq, bk)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p, alpha = jnp.exp(s - m_new), jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                                                      preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(o_ref.shape).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(l_ref[...])).reshape(lse_ref.shape)


def _attention_specs(group, bq, bk, size, v_size, keys_inner: bool):
    """Block specs of (q-like (size), q-like (v_size), per-row scalar, k, v, mask)
    for a grid (kv head, query tile, key tile) or, ``keys_inner`` false, (kv
    head, key tile, query tile); a step above the diagonal asks for the blocks
    of the nearest step that runs."""
    if keys_inner:
        at = lambda g, i, j: (i, jnp.minimum(j, (i * bq + bq - 1) // bk))
    else:
        at = lambda g, j, i: (jnp.maximum(i, (j * bk) // bq), j)
    rows = lambda last: pl.BlockSpec((None, group, bq, last), lambda g, a, b: (g, 0, at(g, a, b)[0], 0))
    keys = lambda last: pl.BlockSpec((None, bk, last), lambda g, a, b: (g, at(g, a, b)[1], 0))
    return rows(size), rows(v_size), rows(1), keys(size), keys(v_size), pl.BlockSpec((bq, bk), lambda g, a, b: at(g, a, b))


def masked_attention(q, k, v, mask, interpret: bool = False, tiles: tuple[int, int] | None = None):
    """``q`` (heads, T, size) scaled, ``k`` (kv_heads, T, size), ``v`` (kv_heads, T,
    value size), ``mask`` (T, T) int8 with a key for every query and none above
    the diagonal -> ``(out (heads, T, value size) in q's dtype, lse (heads, T)
    float32)``."""
    heads, t, size = q.shape
    kv_heads, v_size = k.shape[0], v.shape[-1]
    group = heads // kv_heads
    bq, bk = tiles or (_tile(t, ATTENTION_TILE[0]), _tile(t, ATTENTION_TILE[1]))
    q_spec, o_spec, row_spec, k_spec, v_spec, mask_spec = _attention_specs(group, bq, bk, size, v_size, True)
    out, lse = pl.pallas_call(
        functools.partial(_attention_fwd_kernel, group=group, bq=bq, bk=bk, nk=t // bk),
        grid=(kv_heads, t // bq, t // bk),
        in_specs=[q_spec, k_spec, v_spec, mask_spec],
        out_specs=[o_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((kv_heads, group, t, v_size), q.dtype),
                   jax.ShapeDtypeStruct((kv_heads, group, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group * bq, 1), jnp.float32), pltpu.VMEM((group * bq, 1), jnp.float32),
                        pltpu.VMEM((group * bq, v_size), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        name="dsa_attention_fwd", interpret=interpret,
    )(_grouped(q, kv_heads), k, v, mask)
    return out.reshape(heads, t, v_size), lse.reshape(heads, t)


def _probabilities_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, group, bq, bk):
    """One step of the backward pass: ``(P, dS, q rows, dO rows)``, the first two
    (group x bq, bk) in the operands' dtype."""
    s, q = _group_scores(q_ref, k_ref, mask_ref, group, bq, bk)
    rows = group * bq
    p = jnp.exp(s - lse_ref[...].reshape(rows, 1))
    do = do_ref[...].reshape(rows, do_ref.shape[-1])
    dp = jax.lax.dot_general(do, v_ref[...], _NT, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[...].reshape(rows, 1))
    return p.astype(q.dtype), ds.astype(q.dtype), q, do


def _attention_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, mask_ref, dq_ref, acc_ref, *, group, bq, bk,
                         nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _():
        _, ds, _, _ = _probabilities_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, group, bq, bk)
        acc_ref[...] += jnp.dot(ds, k_ref[...], preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[...] = acc_ref[...].reshape(dq_ref.shape).astype(dq_ref.dtype)


def _attention_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, mask_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          group, bq, bk, nq):
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _():
        p, ds, q, do = _probabilities_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, group, bq, bk)
        dv_acc[...] += jax.lax.dot_general(p, do, _TN, preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(ds, q, _TN, preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def masked_attention_bwd(q, k, v, mask, out, lse, d_out, interpret: bool = False,
                         tiles: tuple[int, int] | None = None):
    """The gradients of ``masked_attention``'s output for its ``q``, ``k``, ``v``."""
    heads, t, size = q.shape
    kv_heads, v_size = k.shape[0], v.shape[-1]
    group = heads // kv_heads
    bq, bk = tiles or (_tile(t, ATTENTION_TILE[0]), _tile(t, ATTENTION_TILE[1]))
    delta = jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32), axis=-1, keepdims=True)
    operands = (_grouped(q, kv_heads), _grouped(d_out, kv_heads), _grouped(lse[..., None], kv_heads),
                _grouped(delta, kv_heads), k, v, mask)
    params = lambda *semantics: pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT)

    q_spec, o_spec, row_spec, k_spec, v_spec, mask_spec = _attention_specs(group, bq, bk, size, v_size, True)
    dq = pl.pallas_call(
        functools.partial(_attention_dq_kernel, group=group, bq=bq, bk=bk, nk=t // bk),
        grid=(kv_heads, t // bq, t // bk),
        in_specs=[q_spec, o_spec, row_spec, row_spec, k_spec, v_spec, mask_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((kv_heads, group, t, size), q.dtype),
        scratch_shapes=[pltpu.VMEM((group * bq, size), jnp.float32)],
        compiler_params=params("parallel", "parallel", "arbitrary"),
        name="dsa_attention_dq", interpret=interpret,
    )(*operands)

    q_spec, o_spec, row_spec, k_spec, v_spec, mask_spec = _attention_specs(group, bq, bk, size, v_size, False)
    dk, dv = pl.pallas_call(
        functools.partial(_attention_dkv_kernel, group=group, bq=bq, bk=bk, nq=t // bq),
        grid=(kv_heads, t // bk, t // bq),
        in_specs=[q_spec, o_spec, row_spec, row_spec, k_spec, v_spec, mask_spec],
        out_specs=[k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, size), jnp.float32), pltpu.VMEM((bk, v_size), jnp.float32)],
        compiler_params=params("parallel", "parallel", "arbitrary"),
        name="dsa_attention_dkv", interpret=interpret,
    )(*operands)
    return dq.reshape(heads, t, size), dk, dv
