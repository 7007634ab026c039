"""Causal softmax attention inside packed documents, grouped-query:

    out_i = sum_j softmax_j(scale * q_i . k_j) v_j   over j <= i with seg_j == seg_i

(query head ``h`` reads key/value head ``h // (heads / kv_heads)``; every
query sees itself, so no row is empty).  The value head may be narrower
than the query/key head (latent attention: 192 against 128), natively in
both blockings: nothing is padded.  One algorithm, two blockings:

- ``xla``: the queries in blocks of ``xla_q_block``, block ``i`` against the
  keys ``[0, end of block i)``, each block recomputed in the backward pass.
  A block's float32 scores ``(heads, block, keys)`` go through HBM: written
  by the first matmul, read by the mask and the softmax, written again as
  probabilities and read by the second matmul.
- ``kernel``: the blocked TPU kernel jax ships
  (``jax.experimental.pallas.ops.tpu.splash_attention``; causal mask, segment
  ids, grouped heads without repeating k and v, its own forward, dq and dk/dv
  kernels).  A block of scores lives in VMEM only, the softmax is the online
  one (running maximum and sum), and the block pairs above the diagonal are
  skipped when the kernel is built.

Which one runs is ``lowering``'s answer, from the backend and the shapes
alone.

Precision, both paths: q, k, v in the caller's dtype (``config.dtype``,
bfloat16); scores, maximum, sum and the output accumulator float32; in the
backward pass the probabilities and ``dS`` are rounded to that dtype for
their matmuls, which accumulate in float32.  In the forward pass the xla
path rounds the probabilities to that dtype before the product with the
values; the kernel leaves them float32 there (closer to the float32
reference, never further).  The kernel masks with a large finite value where
the xla path writes ``-inf``: the same result, since no row is empty.  The
kernel takes no scale, so ``q`` is scaled beforehand, in float32 and rounded
once to the dtype: exact for a power of two (the published 0.015625, the
tiny preset's 0.25), one more rounding of ``q`` for any other value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KERNEL, XLA = "kernel", "xla"

# The kernel's blocks of queries and of keys (forward, dk/dv kernel, dq kernel) and
# the forward's sub-block of keys per pass of the online softmax.
#
# MEASURED (v5e-1, PR 27: 32 query / 8 key-value heads of 64, T = 8192, one
# sequence of 18 documents, ms per call with the scaling and the four
# transposes; the xla path 20.98 forward, 75.46 forward + backward):
#   all 1024                                  forward 5.36, backward 14.51
#   forward 512x512 / 1024x512 / 512x1024     5.46 / 5.42 / 5.60
#   forward 1024x1024, keys 512 a pass        4.86  <- taken (256 a pass: 5.17)
#   dk/dv 512x512 / 1024x512 / 512x1024       backward 16.56 / 15.36 / 15.09
#   dq 512x512 / 1024x512 / 512x1024          backward 15.65 / 14.84 / 14.90
#   2048 queries: the dq kernel wants 17.45 MB of 16 MB scoped VMEM; forward 2048x512 5.69
# The kernel's fused backward (dq inside the dk/dv kernel) reads 11.18 but keeps
# dq's partial sums per key block in bfloat16: not the precision promised above.
BLOCK_SIZES = dict(block_q=1024, block_kv=1024, block_kv_compute=512,
                   block_q_dkv=1024, block_kv_dkv=1024, block_q_dq=1024, block_kv_dq=1024)


def lowering(backend: str, seq_len: int) -> str:
    """``kernel`` where the blocked kernel can run: a TPU backend and a
    sequence of whole blocks; ``xla`` everywhere else (the CPU, a short or
    ragged sequence)."""
    whole_blocks = seq_len > 0 and all(seq_len % b == 0 for b in BLOCK_SIZES.values())
    return KERNEL if backend == "tpu" and whole_blocks else XLA


def packed_causal_attention(q, k, v, segment_ids, scale: float, xla_q_block: int):
    """``q`` (batch, T, heads, head size), ``k`` (batch, T, kv_heads, head
    size), ``v`` (batch, T, kv_heads, value head size), ``segment_ids``
    (batch, T) with every document a contiguous run of one id ->
    (batch, T, heads, value head size) in ``q``'s dtype."""
    if lowering(jax.default_backend(), q.shape[1]) == KERNEL:
        return _kernel_path(q, k, v, segment_ids, scale)
    return _xla_path(q, k, v, segment_ids, scale, xla_q_block)


def _xla_path(q, k, v, segment_ids, scale, q_block):
    batch, t, heads, hd = q.shape
    kvh = k.shape[2]
    q = q.reshape(batch, t, kvh, heads // kvh, hd)

    def block(q_blk, seg_q, start, k_seen, v_seen, seg_k):
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_seen, preferred_element_type=jnp.float32)
        pos_q = start + jnp.arange(q_blk.shape[1])
        allowed = (pos_q[:, None] >= jnp.arange(k_seen.shape[1])[None, :]) & (
            seg_q[:, :, None] == seg_k[:, None, :])  # (b, q, s)
        scores = jnp.where(allowed[:, None, None], scores * scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v_seen)

    block = jax.checkpoint(block, static_argnums=(2,))  # scores are recomputed, never kept
    out = [block(q[:, s:s + q_block], segment_ids[:, s:s + q_block], s, k[:, :min(s + q_block, t)],
                 v[:, :min(s + q_block, t)], segment_ids[:, :min(s + q_block, t)])
           for s in range(0, t, q_block)]
    return jnp.concatenate(out, axis=1).reshape(batch, t, heads, v.shape[-1])


def _kernel_path(q, k, v, segment_ids, scale, interpret: bool = False):
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    t, heads = q.shape[1], q.shape[2]
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([splash.CausalMask((t, t))] * heads),
        block_sizes=splash.BlockSizes(**BLOCK_SIZES), head_shards=1, q_seq_shards=1, interpret=interpret)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    out = jax.vmap(lambda q1, k1, v1, seg: kernel(q1, k1, v1, splash.SegmentIds(q=seg, kv=seg)))(
        heads_first(q), heads_first(k), heads_first(v), segment_ids)
    return heads_first(out)


def block_pair_counts(segment_ids, block_q: int, block_kv: int) -> tuple[int, int]:
    """On the host, for a batch's ``segment_ids`` (batch, T): the (query
    block, key block) pairs the causal kernel computes (those holding a pair
    ``j <= i``), and how many of them hold at least one pair of the same
    document.  The difference is what skipping by document would leave out.

    Documents are contiguous runs, so a key block that ends before a query
    block begins shares a document with it exactly when its last token and
    the query block's first token do."""
    seg = np.asarray(segment_ids)
    t = seg.shape[1]
    q_first, k_first = np.arange(0, t, block_q), np.arange(0, t, block_kv)
    q_last, k_last = q_first + block_q - 1, k_first + block_kv - 1
    computed = k_first[None, :] <= q_last[:, None]
    meets_itself = computed & (k_last[None, :] >= q_first[:, None])
    same = seg[:, q_first][:, :, None] == seg[:, k_last][:, None, :]
    return seg.shape[0] * int(computed.sum()), int((computed & (meets_itself | same)).sum())
