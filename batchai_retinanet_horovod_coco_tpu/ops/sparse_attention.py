"""Learned sparse attention (DeepSeek Sparse Attention, as Keye-VL-2.0's
``sa_config`` sets it up): a small INDEXER scores every (query, key) pair, each
query keeps the ``topk`` best keys of its own document's causal past, and the
main grouped-query attention runs over those keys alone:

    I[t, s]  = scale_I * sum_j w[t, j] * relu(qI[t, j] . kI[s])            (``index_scores``)
    S_t      = the topk positions s <= t of t's document with the largest I[t, s]
               (all of them where there are fewer; ties to the lower position)   (``thresholds``, ``selection_mask``)
    out[t,a] = sum_{s in S_t} softmax_{s in S_t}(scale * q[t,a] . k[s,g(a)]) v[s,g(a)]
    P[t, s]  = mean over the query heads a of those probabilities (no gradient)
    L_I      = mean_t KL(P[t, .] || softmax_{s in S_t} I[t, .])              (``indexer_kl``)

The pairs attention may use are computed from activations, so nothing of the
mask is known when the step is built: the selection is an array of the step.

**The selection is exact and sorts nothing.**  A float's bit pattern, with the
negative ones turned over, orders like the float; the k-th largest score of a
query is found by bisection over that pattern, one bit a pass, 32 counting
passes over the query's scores (``_kth_largest``).  The selection is then
``I > tau_t``, and of the keys that tie with ``tau_t`` the lowest positions
that fill the ``topk`` (``cut``: the position of the last tie taken) - what
``lax.top_k`` picks.  ``tau`` and ``cut`` are (batch, T) integers: they carry
the checkpoint name ``THRESHOLD`` so that a layer recomputed in the backward
pass may keep them and form the selection again by comparison alone.  The
kernel lowering's attention gives its output and log-sum-exp, the residuals
of its dq and dk/dv kernels, the name ``attention.RESIDUALS`` INSIDE its
forward rule (a residual is the rule's own value: a name given outside the
``custom_vjp`` names another), so that such a layer keeps them too and the
forward kernel is not run again in the backward pass: 136 MB a layer at
T = 16 384, 0.3 ms to write and read back against 27 ms to form again.
``lm_layers.layer_keeps`` gives the policy that keeps both names (and, where the
device has the room, ``lm_layers.MLP_GATE_UP``).

Two lowerings, chosen by ``lowering`` from the backend and the shapes alone:

- ``xla``: everything above in ``jax.numpy``, the queries in blocks of
  ``q_block`` whose temporaries are recomputed in the backward pass; the
  selected attention is ``ops/attention.py``'s xla blocking with one more mask
  (with ``topk >= T`` it is that function's arithmetic, bit for bit).
- ``kernel`` (a TPU, a sequence of whole blocks): ops/pallas/dsa.py.  The queries
  go in RUNS of ``KERNEL_ROWS``: a run's index scores (``index_scores``: the
  sixteen heads' products, ReLU, weights and sum on one tile in VMEM, so the
  (heads, T, T) intermediate never exists; their gradient by a dq and a dk
  kernel that form a tile's products again), its thresholds (``thresholds``: a
  query tile's scores stay in VMEM for all the passes) and its rows of the
  selection, an int8 (T, T) array shared by all heads.  The attention itself is
  ``masked_attention``, blocked with a running maximum and sum, all the query
  heads of a key-value head in one step, forward, dq and dk/dv kernels.  WHICH
  BLOCKS RUN IS THE CAUSAL LIST, WHATEVER WAS SELECTED: a block that holds no
  selected pair is computed under its mask like any other, so the step's work
  does not follow the seed.  ``P`` a run at a time from the kernel's
  log-sum-exp (``mean_probs``), beside the run's scores formed again: neither
  the (T, T) scores nor ``P`` ever exist whole, and the backward pass forms a
  run's again.

Precision: the indexer's operands in the caller's dtype (bfloat16), products
accumulated in float32, ReLU, weights, sum and scale float32; scores and
thresholds float32 bit patterns; the main attention as ``ops/attention.py``
states; ``P``, the KL and its softmax float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from batchai_retinanet_horovod_coco_tpu.ops import attention
from batchai_retinanet_horovod_coco_tpu.ops.pallas import dsa

KERNEL, XLA = "kernel", "xla"
THRESHOLD = "dsa_threshold"
INT32_MIN = np.int32(-(2 ** 31))
# the bit a pass of the bisection decides, as int32 patterns, the sign bit first
_BITS = np.array([1 << b for b in range(31, -1, -1)], np.uint32).view(np.int32)
KERNEL_ROWS = 2048  # queries of one run of the kernel lowering (its scores: 134 MB at T = 16 384)


class Thresholds(NamedTuple):
    tau: jax.Array  # (batch, T) int32: the ordered pattern of the topk-th largest allowed score of a query
    cut: jax.Array  # (batch, T) int32: keys that tie with tau are taken up to this position
    tied: jax.Array  # (batch, T) bool: more keys tie with tau than the selection has room for


class Attended(NamedTuple):
    out: jax.Array  # (batch, T, heads, value size) in q's dtype
    kl: jax.Array  # () float32: L_I, the mean over the batch's queries
    selected: jax.Array  # () float32: the pairs selected
    tied: jax.Array  # () float32: the queries whose ties with the threshold were cut
    mask: jax.Array | None  # (batch, T, T) bool where asked for: the selection


def lowering(backend: str, seq_len: int) -> str:
    """``kernel`` where ``ops/attention.py`` would take its kernel (a TPU and a
    sequence of whole blocks, which are whole runs too); ``xla`` everywhere else."""
    whole = attention.lowering(backend, seq_len) == attention.KERNEL and seq_len % KERNEL_ROWS == 0
    return KERNEL if whole else XLA


def allowed_pairs(segment_ids, rows: slice | None = None, keys: int | None = None):
    """(batch, T) -> (batch, rows, keys) bool: key ``s <= t`` of query ``t``'s
    document, for the queries in ``rows`` (all) and the first ``keys`` keys."""
    t = segment_ids.shape[1]
    rows = rows or slice(0, t)
    pos_q, pos_k = jnp.arange(t)[rows], jnp.arange(keys or t)
    return (pos_q[:, None] >= pos_k[None, :]) & (segment_ids[:, rows, None] == segment_ids[:, None, :keys or t])


def sparse_attention(q, k, v, q_idx, k_idx, w, segment_ids, *, topk: int, scale: float, index_scale: float,
                     how: str = XLA, q_block: int = 1024, with_mask: bool = False, interpret: bool = False,
                     tiles: dict | None = None) -> Attended:
    """``q`` (batch, T, heads, size), ``k``, ``v`` (batch, T, kv_heads, size) the
    main attention's; ``q_idx`` (batch, T, index heads, index size), ``k_idx``
    (batch, T, index size), ``w`` (batch, T, index heads) float32 the indexer's
    (the caller cut their gradient off the layer's input); ``segment_ids``
    (batch, T).  Each part under its named scope: ``indexer`` (the scores),
    ``select``, ``attention_core``, ``indexer_loss``."""
    if how == KERNEL:
        parts = [_kernel_sequence(*args, topk, scale, index_scale, interpret, tiles or {})
                 for args in zip(q, k, v, q_idx, k_idx, w, segment_ids)]
        out, kl, selected, tied, mask = (jnp.stack(x) for x in zip(*parts))
        return Attended(out, jnp.mean(kl), jnp.sum(selected), jnp.sum(tied), mask != 0 if with_mask else None)
    with jax.named_scope("indexer"):
        scores = index_scores(q_idx, k_idx, w, index_scale, q_block)
    with jax.named_scope("select"):
        found = thresholds(scores, segment_ids, topk, q_block)
        mask = selection_mask(scores, segment_ids, found)
    with jax.named_scope("attention_core"):
        out, probs = selected_attention(q, k, v, mask, scale, q_block)
    with jax.named_scope("indexer_loss"):
        kl = indexer_kl(scores, mask, probs, q_block)
    return Attended(out, kl, jnp.sum(mask, dtype=jnp.float32), jnp.sum(found.tied, dtype=jnp.float32),
                    mask if with_mask else None)


# ---- the xla lowering --------------------------------------------------------------


def index_scores(q_idx, k_idx, w, scale: float, q_block: int = 512):
    """-> (batch, T, T) float32 scores of EVERY pair (what is allowed is the
    selection's to say)."""
    t = q_idx.shape[1]
    block = jax.checkpoint(functools.partial(_scores_block, scale=scale))  # (heads, block, T) is never kept
    return jnp.concatenate([block(q_idx[:, s:s + q_block], w[:, s:s + q_block], k_idx)
                            for s in range(0, t, q_block)], axis=1)


def _scores_block(q_blk, w_blk, k_idx, *, scale):
    z = jnp.einsum("bqhd,bsd->bhqs", q_blk, k_idx, preferred_element_type=jnp.float32)
    return scale * jnp.sum(w_blk.astype(jnp.float32).transpose(0, 2, 1)[..., None] * jax.nn.relu(z), axis=1)


def ordered_keys(scores, allowed):
    """float32 scores -> int32 that order as the scores do (``-0.0`` and ``0.0``
    one value); a pair that is not allowed gets the least int32."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    keys = jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)
    return jnp.where(allowed, jnp.where(scores == 0, 0, keys), INT32_MIN)


def _kth_largest(keys, k: int):
    """The largest int32 ``tau`` of which at least ``k`` of ``keys``' last axis
    are no less (the k-th largest of them; the least int32 where there are
    fewer than ``k``): 32 passes, each deciding one bit of ``tau``'s pattern
    taken as unsigned, the highest first."""
    def decide(i, pattern):
        candidate = pattern | jnp.asarray(_BITS)[i]
        enough = jnp.sum(keys >= (candidate ^ INT32_MIN)[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, pattern)

    return jax.lax.fori_loop(0, 32, decide, jnp.zeros(keys.shape[:-1], jnp.int32)) ^ INT32_MIN


def _block_thresholds(scores_blk, allowed_blk, topk: int):
    keys = ordered_keys(scores_blk, allowed_blk)
    tau = _kth_largest(keys, topk)
    above = jnp.sum(keys > tau[..., None], axis=-1, dtype=jnp.int32)
    ties = (keys == tau[..., None]) & allowed_blk
    room = topk - above
    running = jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
    cut = jnp.sum(running < room[..., None], axis=-1, dtype=jnp.int32)  # the position of the room-th tie
    return tau, cut, running[..., -1] > room


def _named(found) -> Thresholds:
    return Thresholds(*(checkpoint_name(x, THRESHOLD) for x in found))


def thresholds(scores, segment_ids, topk: int, q_block: int = 512) -> Thresholds:
    """Per query the ``topk``-th largest allowed score and where its ties are
    cut: ``scores`` (batch, T, T) float32, ``segment_ids`` (batch, T)."""
    scores = jax.lax.stop_gradient(scores)
    t = scores.shape[1]
    parts = []
    for s in range(0, t, q_block):
        rows, keys = slice(s, min(s + q_block, t)), min(s + q_block, t)
        parts.append(_block_thresholds(scores[:, rows, :keys], allowed_pairs(segment_ids, rows, keys), topk))
    return _named(jnp.concatenate(x, axis=1) for x in zip(*parts))


def selection_mask(scores, segment_ids, found: Thresholds, rows: slice | None = None):
    """(batch, rows, T) bool: the pairs selected, by comparison with the
    thresholds alone; ``scores`` and ``found`` of the queries in ``rows`` (all)."""
    allowed = allowed_pairs(segment_ids, rows)
    keys = ordered_keys(jax.lax.stop_gradient(scores), allowed)
    tau, cut = found.tau[..., None], found.cut[..., None]
    return allowed & ((keys > tau) | ((keys == tau) & (jnp.arange(scores.shape[-1]) <= cut)))


def selected_attention(q, k, v, mask, scale: float, q_block: int = 1024):
    """``ops/attention.py::_xla_path`` with the selection (batch, T, T) bool as
    its mask (every query has a key), and beside the output (batch, T, heads,
    size) ``P`` (batch, T, T) float32: the mean over the query heads of the
    probabilities (zero off the selection; no gradient)."""
    batch, t, heads, hd = q.shape
    kvh = k.shape[2]
    q = q.reshape(batch, t, kvh, heads // kvh, hd)

    def block(q_blk, mask_blk, k_seen, v_seen):
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_seen, preferred_element_type=jnp.float32)
        scores = jnp.where(mask_blk[:, None, None], scores * scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(q_blk.dtype), v_seen)
        return out, jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))

    block = jax.checkpoint(block)  # scores are recomputed, never kept
    out, probs = [], []
    for s in range(0, t, q_block):
        end = min(s + q_block, t)
        o, p = block(q[:, s:end], mask[:, s:end, :end], k[:, :end], v[:, :end])
        out.append(o)
        probs.append(jnp.pad(p, [(0, 0), (0, 0), (0, t - end)]))
    return jnp.concatenate(out, axis=1).reshape(batch, t, heads, v.shape[-1]), jnp.concatenate(probs, axis=1)


def _kl_sum(scores_blk, mask_blk, target_blk):
    """Sum over the block's queries of ``KL(target || softmax over the mask of scores)``."""
    logits = jnp.where(mask_blk, scores_blk, -jnp.inf)
    log_q = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    counted = mask_blk & (target_blk > 0)
    log_ratio = jnp.log(jnp.where(counted, target_blk, 1.0)) - jnp.where(counted, log_q, 0.0)
    return jnp.sum(jnp.where(counted, target_blk * log_ratio, 0.0))


def indexer_kl(scores, mask, target, q_block: int = 512):
    """``mean_t KL(target[t, .] || softmax_{s in S_t} scores[t, .])`` over all
    the batch's queries: ``scores``, ``target`` (batch, T, T) float32, ``mask``
    (batch, T, T) bool; ``target`` sums to one over a query's selection and
    carries no gradient.  The queries in blocks, each formed again in the
    backward pass."""
    target = jax.lax.stop_gradient(target)
    batch, t = scores.shape[:2]
    block = jax.checkpoint(_kl_sum)
    total = sum(block(scores[:, s:s + q_block, :min(s + q_block, t)], mask[:, s:s + q_block, :min(s + q_block, t)],
                      target[:, s:s + q_block, :min(s + q_block, t)]) for s in range(0, t, q_block))
    return total / (batch * t)


# ---- the kernel lowering -------------------------------------------------------------


def _kernel_sequence(q, k, v, q_idx, k_idx, w, seg, topk, scale, index_scale, interpret, tiles):
    """One sequence: -> (out (T, heads, size), the sum over its queries of the
    KL / T, the pairs selected, the queries whose ties were cut, the selection
    (T, T) int8)."""
    t = q.shape[0]
    rows = tiles.get("rows", KERNEL_ROWS)
    runs = [(row0, slice(row0, row0 + rows)) for row0 in range(0, t, rows)]
    score = lambda row0, q_idx_r, w_r: _kernel_scores(interpret, index_scale, row0, tiles.get("scores"), q_idx_r, k_idx, w_r)
    found, mask = [], []
    for row0, r in runs:
        with jax.named_scope("indexer"):
            scores = jax.lax.stop_gradient(score(row0, q_idx[r], w[r]))
        with jax.named_scope("select"):
            found.append(_named(dsa.thresholds(scores, seg, topk, row0, interpret, **tiles.get("thresholds", {}))))
            mask.append(selection_mask(scores[None], seg[None], Thresholds(*(x[None] for x in found[-1])), r)[0]
                        .astype(jnp.int8))
    with jax.named_scope("select"):
        mask = jnp.concatenate(mask)
        tied = sum(jnp.sum(f.tied, dtype=jnp.float32) for f in found)
    with jax.named_scope("attention_core"):
        scaled = (q.astype(jnp.float32) * scale).astype(q.dtype)  # the kernels take no scale (ops/attention.py's note)
        qh, kh, vh = (x.transpose(1, 0, 2) for x in (scaled, k, v))
        out, lse = _masked_attention(interpret, tiles.get("attention"), qh, kh, vh, mask)

    def run_kl(row0, q_idx_r, w_r, mask_r, qh_r, lse_r):
        with jax.named_scope("indexer"):  # a run's scores again: neither they nor the target ever exist whole
            scores = score(row0, q_idx_r, w_r)
        with jax.named_scope("indexer_loss"):
            probs = dsa.mean_probs(qh_r, jax.lax.stop_gradient(kh), lse_r, mask_r, row0, interpret, tiles.get("probs"))
            return _kl_sum(scores, mask_r != 0, probs)

    stop = jax.lax.stop_gradient
    kl = sum(jax.checkpoint(run_kl, static_argnums=(0,))(row0, q_idx[r], w[r], mask[r], stop(qh[:, r]), stop(lse[:, r]))
             for row0, r in runs) / t
    return out.transpose(1, 0, 2), kl, jnp.sum(mask, dtype=jnp.float32), tied, mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _kernel_scores(interpret, scale, row0, tile, q_idx, k_idx, w):
    """The scores (rows, T) of the queries from ``row0`` on by the tile kernel;
    the gradient by its dq and dk kernels, against the keys up to the run's last
    query (nothing above the diagonal is ever selected)."""
    return dsa.index_scores(q_idx, k_idx, w, scale, row0, interpret, tile)


def _kernel_scores_fwd(interpret, scale, row0, tile, q_idx, k_idx, w):
    return _kernel_scores(interpret, scale, row0, tile, q_idx, k_idx, w), (q_idx, k_idx, w)


def _kernel_scores_bwd(interpret, scale, row0, tile, res, d_scores):
    q_idx, k_idx, w = res
    dq, dk, dw = dsa.index_scores_bwd(q_idx, k_idx, w, d_scores, scale, row0, interpret, tile)
    return dq, dk.astype(k_idx.dtype), dw


_kernel_scores.defvjp(_kernel_scores_fwd, _kernel_scores_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _masked_attention(interpret, tiles, q, k, v, mask):
    """``(out, log-sum-exp)`` of ``dsa.masked_attention``; the log-sum-exp feeds
    ``P`` alone, which carries no gradient."""
    return dsa.masked_attention(q, k, v, mask, interpret, tiles)


def _masked_attention_fwd(interpret, tiles, q, k, v, mask):
    # named here, before they are the rule's outputs and residuals: a recomputed layer keeps them
    out, lse = (checkpoint_name(x, attention.RESIDUALS) for x in dsa.masked_attention(q, k, v, mask, interpret, tiles))
    return (out, lse), (q, k, v, mask, out, lse)


def _masked_attention_bwd(interpret, tiles, res, cotangents):
    q, k, v, mask, out, lse = res
    return (*dsa.masked_attention_bwd(q, k, v, mask, out, lse, cotangents[0], interpret, tiles), None)


_masked_attention.defvjp(_masked_attention_fwd, _masked_attention_bwd)
