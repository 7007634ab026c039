"""The gated delta rule of a linear-attention mixer (Gated DeltaNet: Yang, Kautz,
Hatamizadeh, arXiv:2412.06464), chunk by chunk, with the state reset at document
boundaries.

The recurrence, per head (``k_t``, ``q_t`` of size K, ``v_t`` of size V, the decay
``a_t = exp(log_a_t)`` in (0, 1], the writing strength ``b_t`` in [0, 2]):

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T,   S = 0 before a document's first token
    o_t = S_t q_t

``S`` is a (V, K) matrix: the decayed state forgets what it held along ``k_t`` and
writes ``v_t`` there.  With ``u_t = b_t (v_t - a_t S_{t-1} k_t)`` (the value
corrected by what the state already returns for this key) the update is the
outer product ``S_t = a_t S_{t-1} + u_t k_t^T``, but ``u_t`` depends on every
earlier token of the chunk: unlike ops/ssd.py's scan, token j's contribution to
token i is no product of two vectors and a decay.  The chunked form (Yang et al.,
arXiv:2406.06484 section 3; with the decay, arXiv:2412.06464 section 3.3) is
exact: inside a chunk of C tokens that starts from the state ``S``, with ``g`` the
inclusive cumulative sum of ``log_a`` inside the chunk,

    (I + L) U = b (V - r K S^T),   L_ij = b_i exp(g_i - g_j) (k_i . k_j) for i > j in one document

(a unit-lower-triangular system: ``U = u - w S^T`` with ``u = (I + L)^-1 b V`` and
``w = (I + L)^-1 b r K``), then

    O  = r (Q S^T) + (D * Q K^T) U,    D_ij = exp(g_i - g_j) for i >= j in one document
    S' = keep S + U^T (e K)

``r_i = exp(g_i)`` where token i is still in the document ``S`` belongs to (else 0),
``e_j = exp(g_C - g_j)`` where token j is in the chunk's last document, ``keep =
exp(g_C)`` if that is still ``S``'s document.  Documents are contiguous runs of one
segment id, so every reset is a mask, in the triangular system and in the causal
products alike, and the carried state is dropped by ``keep`` and ``r``: nothing is
divided by a decay or exponentiated above zero.

Precision: ``log_a``, ``b``, the cumulative sums, masks, ``L``, the system's
solution ``U`` (the product of the system's inverse with its right-hand side too:
where keys repeat it is a small difference of large terms) and the carried state
float32; the operands of the other products are rounded to q's dtype and
accumulate in float32.

One algorithm, two lowerings; ``lowering`` says which runs, from the backend and
the shapes alone:

- ``xla``: the body below.  Masks, ``D``, ``L`` and the solved system for every
  (chunk, head) at once (``solve_triangular``), then a ``lax.scan`` over the chunks
  that carries the state; differentiated by JAX.  The (chunks, heads, C, C)
  matrices go through HBM.
- ``kernel``: ops/pallas/delta_rule.py, a forward and a backward Pallas TPU
  kernel under a ``custom_vjp`` that take over after the cumulative sums: a block
  of heads' chunks run in order with the state in VMEM; ``D``, ``L`` and the
  system's inverse never leave VMEM; the forward saves one state a chunk for
  the backward, which walks the chunks in reverse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from batchai_retinanet_horovod_coco_tpu.ops.pallas import delta_rule as kernel_lib

KERNEL, XLA = "kernel", "xla"
_NEG_INF = -jnp.inf

# The kernels' chunk and heads a grid step (ops/pallas/delta_rule.py::HEADS_PER_BLOCK), and how the float32
# products of the triangular system's recursion are multiplied.
#
# MEASURED (v5e-1, PR 40: 30 heads of key 96 / value 192, T = 8192, one sequence of 8 documents (the cell's first
# pooled batch), bfloat16 q, k, v; ms per call of ``_chunked`` with its cumulative sum and the layout changes around
# the kernels, forward / forward + backward; Mosaic compiles the backward kernel in 28-31 s, the forward in 1-2 s):
#   xla lowering, chunks of 128                  24.10 / 36.98
#   kernels, chunks of 128, blocks of 6 heads    5.96 / 13.01  <- taken (three bfloat16 passes a float32 product)
#     blocks of 3 / 5 / 10 heads                 6.05 / 13.36, 6.14 / 13.19, 5.75 / 12.64 (2 s more to compile: 0.3% of
#                                                the cell's step for 2 s of its set-up a kernel)
#     float32 products at Precision.HIGHEST      8.11 / 17.15 (six passes; the output moves by 1.2e-4 of its norm to
#                                                the three-pass one, under the 2e-3 the bfloat16 operands cost)
#     ONE bfloat16 pass                          5.79 / 12.47: the recursion's twelve products are 3% of the kernels'
#                                                time in three passes, so nothing is gained by a sloppier inverse
#   kernels, chunks of 256                       11.05 / 23.53: the (C, C) work doubles a token, and the recursion has
#                                                a level more
# Those rows were read with the solution ``U = T rhs`` as ONE bfloat16 pass; it is three since (the largest state
# norm of the cell's step moved from within 1.3e-2 of the float32 recurrence's to within 6.3e-3, worst of a dozen
# seeds each): blocks of 6 heads 5.92 / 12.91, of 10 heads 5.67 / 12.53, xla 24.63 / 37.87 (a second call; the
# difference to the first is the two calls', not the pass's).
# Chunks of 64 are not offered: a (64, 64) float32 matrix is half a lane tile wide and the kernels' blocks would
# carry twice the states.  The forward kernel runs twice a layer and step (the layer is recomputed), the backward
# once: 3 layers x (5.92 + 12.91) = 56 ms of the cell's 590 ms step.


def lowering(backend: str, seq_len: int, chunk: int, heads: int, key_dim: int, value_dim: int) -> str:
    """``kernel`` where the kernels can run: a TPU backend, a sequence of whole
    chunks of whole lane tiles (and a power of two: the system's recursion
    halves them), head sizes of whole sublane tiles (16 rows of bfloat16; 96 and
    192 are, and need not be multiples of 128); any number of heads (they go in
    blocks of a divisor).  ``xla`` everywhere else (the CPU, the tiny preset's
    chunks of 8, a ragged sequence)."""
    whole = chunk % 128 == 0 and chunk & (chunk - 1) == 0 and key_dim % 16 == 0 and value_dim % 16 == 0
    return KERNEL if backend == "tpu" and heads > 0 and seq_len > 0 and seq_len % chunk == 0 and whole else XLA


def gated_delta_rule(q, k, v, log_a, b, segment_ids, chunk: int):
    """``(o, state_norm_max)`` of the recurrence above.

    q, k: (batch, T, H, K), already normalised and scaled; v: (batch, T, H, V), in
    one dtype; log_a: (batch, T, H) float32, <= 0; b: (batch, T, H) float32;
    segment_ids: (batch, T) int, constant over a document and different in
    neighbouring documents; chunk: tokens per chunk, a power of two (T is padded
    up to a multiple with tokens of no document).  Returns ``o`` (batch, T, H, V)
    float32 and the largest Frobenius norm of a head's state at a chunk's end (a
    scalar no gradient flows through).
    """
    _, t, heads, key_dim = q.shape
    use_kernel = lowering(jax.default_backend(), t, chunk, heads, key_dim, v.shape[-1]) == KERNEL
    return _chunked(q, k, v, log_a, b, segment_ids, chunk, kernel_lib.chunked_delta_rule if use_kernel else None)


def _chunked(q, k, v, log_a, b, segment_ids, chunk, kernel):
    batch, t, heads, _ = q.shape
    if chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    pad = -t % chunk
    if pad:
        # Padding: a document of its own that writes nothing (b = 0) and decays nothing.
        widths = lambda a: [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        q, k, v, log_a, b = (jnp.pad(a, widths(a)) for a in (q, k, v, log_a, b))
        segment_ids = jnp.pad(segment_ids, [(0, 0), (0, pad)], constant_values=-1)
    nc = (t + pad) // chunk
    g = jnp.cumsum(log_a.astype(jnp.float32).reshape(batch, nc, chunk, heads), axis=2)  # inclusive
    b = b.astype(jnp.float32).reshape(batch, nc, chunk, heads)
    seg = segment_ids.reshape(batch, nc, chunk)
    o, sq = (kernel or _scan_chunks)(q, k, v, g, b, seg)
    return o[:, :t], jnp.sqrt(jnp.max(lax.stop_gradient(sq)))


def _scan_chunks(q, k, v, g, b, seg):
    """The xla lowering: ``(o, sq)`` as ``ops/pallas/delta_rule.py::chunked_delta_rule``."""
    batch, nc, chunk, heads = g.shape
    dtype = q.dtype
    by_chunk = lambda a: jnp.moveaxis(a.reshape(batch, nc, chunk, heads, -1), 3, 2)  # (b, c, h, l, d)
    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    gh, bh = jnp.moveaxis(g, 3, 2), jnp.moveaxis(b, 3, 2)  # (b, c, h, l)

    idx = jnp.arange(chunk)
    same = seg[:, :, :, None] == seg[:, :, None, :]  # (b, c, i, j)
    within = ((idx[:, None] >= idx[None, :]) & same)[:, :, None]
    decay = jnp.exp(jnp.where(within, gh[..., :, None] - gh[..., None, :], _NEG_INF))  # (b, c, h, i, j)
    kk = jnp.einsum("bchid,bchjd->bchij", kc, kc, preferred_element_type=jnp.float32)
    lower = jnp.where(idx[:, None] > idx[None, :], bh[..., None] * decay * kk, 0.0)
    with jax.default_matmul_precision("highest"):
        inverse = jax.scipy.linalg.solve_triangular(
            lower + jnp.eye(chunk, dtype=jnp.float32), jnp.broadcast_to(jnp.eye(chunk, dtype=jnp.float32), lower.shape),
            lower=True, unit_diagonal=True)
    weights = (jnp.einsum("bchid,bchjd->bchij", qc, kc, preferred_element_type=jnp.float32) * decay).astype(dtype)

    seg_last = seg[:, :, -1]  # (b, c)
    seg_prev = jnp.pad(seg_last[:, :-1], [(0, 0), (1, 0)], constant_values=-2)  # no document before the first chunk
    g_last = gh[..., -1:]
    reach = jnp.exp(jnp.where((seg == seg_prev[..., None])[:, :, None], gh, _NEG_INF))  # (b, c, h, l)
    to_end = jnp.exp(jnp.where((seg == seg_last[..., None])[:, :, None], g_last - gh, _NEG_INF))
    keep = jnp.where((seg_last == seg_prev)[:, :, None], jnp.exp(g_last[..., 0]), 0.0)  # (b, c, h)
    k_end = (kc.astype(jnp.float32) * to_end[..., None]).astype(dtype)

    def one_chunk(state, xs):
        qi, ki, vi, bi, inv, w, r, ke, kp = xs
        s_op = state.astype(dtype)  # (b, h, V, K)
        rhs = bi[..., None] * (vi.astype(jnp.float32) - r[..., None] * jnp.einsum(
            "bhlk,bhvk->bhlv", ki, s_op, preferred_element_type=jnp.float32))
        u = jnp.einsum("bhij,bhjv->bhiv", inv, rhs, precision="highest").astype(dtype)  # the system's solution: float32
        o = (r[..., None] * jnp.einsum("bhlk,bhvk->bhlv", qi, s_op, preferred_element_type=jnp.float32)
             + jnp.einsum("bhij,bhjv->bhiv", w, u, preferred_element_type=jnp.float32))
        state = kp[..., None, None] * state + jnp.einsum("bhjv,bhjk->bhvk", u, ke, preferred_element_type=jnp.float32)
        return state, (o, jnp.sum(jnp.square(state), axis=(-2, -1)))

    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)
    state0 = jnp.zeros((batch, heads, v.shape[-1], q.shape[-1]), jnp.float32)
    _, (o, sq) = lax.scan(one_chunk, state0, tuple(
        chunks_first(a) for a in (qc, kc, vc, bh, inverse, weights, reach, k_end, keep)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(batch, nc * chunk, heads, -1)
    return o, jnp.moveaxis(sq, 0, 1)  # (b, c, h)
