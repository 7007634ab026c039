"""Causal softmax attention inside packed documents, grouped-query:

    out_i = sum_j softmax_j(scale * q_i . k_j) v_j   over j <= i with seg_j == seg_i

and, PER CALL (a model's layers may differ: models/afmoe.py's sliding layers
pass ``window``, its full layers none), of those keys only the ``window``
nearest, the query's own among them:

    ... over j <= i with seg_j == seg_i and i - j < window

(query head ``h`` reads key/value head ``h // (heads / kv_heads)``; every
query sees itself, so no row is empty).  The value head may be narrower
than the query/key head (latent attention: 192 against 128), natively in
both blockings: nothing is padded.  One algorithm, two rules (causal in the
document; that and the window), two blockings:

- ``xla``: the queries in blocks of ``xla_q_block``, block ``i`` against the
  keys ``[0, end of block i)``, each block recomputed in the backward pass.
  Under a window the keys are ``[first key the block's first query reaches,
  end of block i)``.  A block's float32 scores ``(heads, block, keys)`` go
  through HBM: written by the first matmul, read by the mask and the softmax,
  written again as probabilities and read by the second matmul.
- ``kernel``: the blocked TPU kernel jax ships
  (``jax.experimental.pallas.ops.tpu.splash_attention``; causal mask, segment
  ids, grouped heads without repeating k and v, its own forward, dq and dk/dv
  kernels).  A block of scores lives in VMEM only and the softmax is the
  online one (running maximum and sum).  Which (query block, key block)
  pairs run is decided twice: those above the diagonal are left out when the
  kernel is built (the static block lists of the mask that is causal inside
  each of the batch's sequences, laid end to end for one call), and those that
  hold no query and key of one document when the step runs: the three
  kernels' block lists are scalar-prefetch operands, computed from the
  step's ``segment_ids`` on the device (``_follow_documents``).  A pair left
  out is a pair whose every score is masked: it adds nothing to the online
  softmax (every query sees itself on the diagonal, which always runs), so
  the outputs and the three gradients are the static lists' numbers, bit
  for bit.  A sequence that is one document runs the causal lists.
  A WINDOW is one more term of the static part and nothing new in the dynamic
  one: a second kernel object (``_causal_kernel`` is keyed on the window too)
  whose mask is the library's ``LocalMask((n, n), (window - 1, 0), 0)`` inside
  each sequence, so its static lists hold only the key blocks a query block's
  window reaches (at T = 16 384 in one document and a window of 2048, 45 of
  the 136 causal pairs of 1024 x 1024 blocks), and ``_follow_documents``
  multiplies them by the step's documents as it does the causal ones.  Window
  layers run the same ``BLOCK_SIZES``.
  The backward kernels take the forward's output and log-sum-exp as
  residuals, and the library gives both the checkpoint name it is built
  with (``residual_checkpoint_name``: ``RESIDUALS``) inside its forward
  rule - a name given to the output outside the ``custom_vjp`` would name
  another value.  A layer recomputed under ``lm_layers.layer_keeps``' policy
  (these residuals, the sparse attention's thresholds and, where the device has
  the room, ``lm_layers.MLP_GATE_UP``) keeps them, so the forward kernel runs
  once a layer and step, not again in the backward pass: 67 MB a layer of
  dsv2's cell written and read back in 0.2 ms against 3.4-3.6 ms to form again.  The xla blocking names nothing.

Which one runs is ``lowering``'s answer, from the backend and the shapes
alone.  The kernel path has two doors: ``head_major_attention`` on operands that
ARE in the kernels' layout ((heads, batch x T, head size), q already scaled:
ops/attention_edges.py's passes write that), and ``_kernel_path``, its caller for
operands as the models hold them, which scales q and transposes each array once
each way, as it always did (``packed_causal_attention``'s callers lower to the
operations they lowered to: tests/unit/test_packed_attention.py).

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

import functools

import jax
import jax.numpy as jnp
import numpy as np

KERNEL, XLA = "kernel", "xla"
RUN_SHARE = "attn/block_pairs_run_share"  # of the layers without a window
WINDOW_RUN_SHARE = "attn/window_block_pairs_run_share"  # of the layers with one
# the checkpoint name of the forward kernel's output and log-sum-exp, the backward kernels' residuals
# (ops/sparse_attention.py's kernels give theirs the same)
RESIDUALS = "attention_residuals"

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
#
# MEASURED (v5e-1, PR 35: the block lists follow the documents; T = 8192, the cells'
# packings of seeds 905418237 / 2100415840, ms of device time per forward +
# backward of a whole batch, forward / dq / dk/dv kernels; "static" = the causal lists):
#   16 / 16 heads of 192 / 128, 2 sequences (dsv2); pairs run 0.535 / 0.504 at 1024, 0.383 / 0.385 at 512
#     static 1024             6.39 / 9.17 / 10.69
#     documents, all 1024     3.58 / 5.14 / 6.27 and 3.41 / 4.89 / 6.02  <- taken
#     documents, all 512      3.94 / 5.05 / 6.33 and 3.95 / 5.06 / 6.33
#     all 1024, a step left out keeps its own block in data_next: 5.01 / 6.38 / 7.44 (it pays the copies)
#     the batch, all operations of a call: static under vmap 30.3; the lists as batched scalar-prefetch
#       operands under vmap (Pallas loops over the batch, slicing and updating whole arrays) 40.6; a Python
#       loop over the sequences 19.5, but XLA fuses no slice or concatenation into a kernel call: dsv2's
#       compiled step writes 4.85 GB a step around the kernels against 2.23 static; the sequences laid end
#       to end, one call a batch (the kernels' rows above were read in the loop): 2.24 GB  <- taken
#   32 / 8 heads of 64, 1 sequence (granite); pairs run 0.542 / 0.569 at 1024, 0.386 / 0.472 at 512
#     static 1024 4.10 / 5.91 / 7.38; documents 1024 2.48 / 3.50 / 4.01 and 2.59 / 3.63 / 4.18;
#     documents 512 2.59 / 3.64 / 4.29 and 2.94 / 4.08 / 4.85
#   32 / 2 heads of 128, 2 sequences (nemo3): static 1024 8.56 / 11.30 / 14.21; documents 1024
#     4.97 / 7.28 / 7.97 and 4.71 / 6.97 / 7.57; documents 512 5.32 / 7.63 / 8.29 and 5.32 / 7.65 / 8.31
# At 512 fewer pairs run and each costs more than a quarter of a 1024 pair: nowhere faster.
BLOCK_SIZES = dict(block_q=1024, block_kv=1024, block_kv_compute=512,
                   block_q_dkv=1024, block_kv_dkv=1024, block_q_dq=1024, block_kv_dq=1024)


# A layer with a WINDOW runs the same seven (PR 46: Trinity-Mini's sliding layers, 2048 keys).  At 1024 x 1024 a
# window of 2048 runs 3 key blocks a query block (45 of the 136 causal pairs at T = 16 384), of which a third of the
# pairs is masked away; at 512 x 512 it would run 5 (150 of 528), of which a fifth.
#
# MEASURED (v5e-1, PR 46: 32 / 4 heads of 128, T = 16 384, one document, a window of 2048; ms of device time a
# forward + backward of one layer, the forward / dq / dk/dv kernels from a trace of five calls, beside 3.5 ms of
# scaling and transposes; every window row's output and three gradients within 0.03 of the 1024 row's, bfloat16):
#   no window, all 1024 (the full layer)      15.42 / 20.81 / 25.98 = 62.2
#   all 1024                                   5.43 /  6.88 /  8.58 = 20.9   <- taken (0.336 of the full layer's; the pairs 0.331)
#   all 512                                    5.12 /  6.61 /  8.15 = 19.9
#   all 512, keys 256 a pass                   6.71 /  6.61 /  8.15
#   queries 1024, keys 512                     5.77 /  7.19 /  9.05
#   queries 512, keys 1024                     5.45 /  7.22 /  8.99
#   forward 2048 x 512, backward 1024 x 512    7.29 /  7.16 /  8.97
#   all 256                                   10.49 / 10.80 / 14.17
#   all 2048: the forward kernel runs out of VMEM (compiled for a described v5e)
# Alone the kernels are 1.0 ms a layer (4.8%) faster at 512; IN THE STEP the same seed read 1.54550 sequences a
# second at 512 against 1.54538 at 1024 (+0.008%, trinity-mini-train-doc16k, my chip runs, PR 46): nothing end to
# end, so no second table of blocks.  Ask again only if a trace shows the window kernels limiting a step.


def lowering(backend: str, seq_len: int) -> str:
    """``kernel`` where the blocked kernel can run: a TPU backend and a
    sequence of whole blocks; ``xla`` everywhere else (the CPU, a short or
    ragged sequence)."""
    whole_blocks = seq_len > 0 and all(seq_len % b == 0 for b in BLOCK_SIZES.values())
    return KERNEL if backend == "tpu" and whole_blocks else XLA


def run_meta(backend: str, seq_len: int, window: int | None = None) -> dict:
    """What a model's ``run_meta`` says of its attention: the lowering, on
    the kernel path that the block lists follow the documents and that a
    recomputed layer keeps the forward kernel's output and log-sum-exp, and
    the window of the layers that have one."""
    path = lowering(backend, seq_len)
    kernel = {"attention_block_skip": "documents", "attention_residuals": "kept"} if path == KERNEL else {}
    return {"attention_lowering": path, **kernel, **({} if window is None else {"attention_window": window})}


def step_counters(segment_ids, window: int | None = None, heads: int | None = None) -> dict:
    """The scalars a step's attention layers add to the model's (a model's
    ``loss`` merges them into what the loop logs): on the kernel path
    ``attn/block_pairs_run_share``, the share of the causal (query block,
    key block) pairs of the forward kernel that run, mean over the step's
    sequences (1.0 = nothing skipped; every attention layer of a step sees
    the same ``segment_ids`` and blocks, so one layer's share is the
    mean over the layers), and for a model whose other layers (of ``heads``
    query heads) have a ``window`` also ``attn/window_block_pairs_run_share``,
    the same share of THEIR forward kernel, counted in the list that kernel
    is given; nothing on the xla path."""
    if lowering(jax.default_backend(), segment_ids.shape[1]) != KERNEL:
        return {}
    full = {RUN_SHARE: block_pairs_run_share(segment_ids, BLOCK_SIZES["block_q"], BLOCK_SIZES["block_kv"])}
    return full if window is None else {**full, WINDOW_RUN_SHARE: _window_run_share(segment_ids, window, heads)}


def _window_run_share(segment_ids, window: int, heads: int):
    """The steps of the window layers' forward grid that run, over the causal
    block pairs: read from the forward list of the kernel object those layers
    call (``_causal_kernel``'s cache holds one a shape and window), cut to the
    step's documents as ``_document_block_lists`` cuts it.  A library whose
    local mask listed every causal block would read what a full layer reads."""
    batch, t = segment_ids.shape
    kernel = _causal_kernel(batch, t, heads, tuple(BLOCK_SIZES.items()), False, window)
    bq, bkv = BLOCK_SIZES["block_q"], BLOCK_SIZES["block_kv"]
    info = kernel.fwd_mask_info
    runs = _runs(np.asarray(info.block_mask)[0], np.asarray(info.data_next)[0],
                 _block_pairs(segment_ids.reshape(batch * t), bq, bkv)[1], False)
    return jnp.sum(runs, dtype=jnp.float32) / (batch * int(_block_pairs(segment_ids, bq, bkv)[0].sum()))


def block_pairs_run_share(segment_ids, block_q: int, block_kv: int):
    """On the device, for ``segment_ids`` (batch, T): the causal block pairs
    that hold a pair of one document over all of them -> float32 scalar."""
    causal, shares = _block_pairs(segment_ids, block_q, block_kv)
    return jnp.sum(shares, dtype=jnp.float32) / (segment_ids.shape[0] * int(causal.sum()))


def packed_causal_attention(q, k, v, segment_ids, scale: float, xla_q_block: int, window: int | None = None):
    """``q`` (batch, T, heads, head size), ``k`` (batch, T, kv_heads, head
    size), ``v`` (batch, T, kv_heads, value head size), ``segment_ids``
    (batch, T) with every document a contiguous run of one id ->
    (batch, T, heads, value head size) in ``q``'s dtype.  ``window``: a query
    sees the ``window`` nearest keys of its causal past in its document,
    itself among them; ``None``: all of it."""
    if lowering(jax.default_backend(), q.shape[1]) == KERNEL:
        return _kernel_path(q, k, v, segment_ids, scale, window=window)
    return _xla_path(q, k, v, segment_ids, scale, xla_q_block, window)


def _xla_path(q, k, v, segment_ids, scale, q_block, window=None):
    batch, t, heads, hd = q.shape
    kvh = k.shape[2]
    q = q.reshape(batch, t, kvh, heads // kvh, hd)

    def block(q_blk, seg_q, start, k_seen, v_seen, seg_k):
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_seen, preferred_element_type=jnp.float32)
        pos_q = start + jnp.arange(q_blk.shape[1])
        if window is None:
            allowed = (pos_q[:, None] >= jnp.arange(k_seen.shape[1])[None, :]) & (
                seg_q[:, :, None] == seg_k[:, None, :])  # (b, q, s)
        else:  # the keys seen end with the block's last query and begin where its first query's window does
            pos_k = start + q_blk.shape[1] - k_seen.shape[1] + jnp.arange(k_seen.shape[1])
            apart = pos_q[:, None] - pos_k[None, :]
            allowed = (apart >= 0) & (apart < window) & (seg_q[:, :, None] == seg_k[:, None, :])
        scores = jnp.where(allowed[:, None, None], scores * scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v_seen)

    block = jax.checkpoint(block, static_argnums=(2,))  # scores are recomputed, never kept
    out = []
    for s in range(0, t, q_block):
        seen = slice(0 if window is None else max(0, s - window + 1), min(s + q_block, t))
        out.append(block(q[:, s:s + q_block], segment_ids[:, s:s + q_block], s, k[:, seen], v[:, seen],
                         segment_ids[:, seen]))
    return jnp.concatenate(out, axis=1).reshape(batch, t, heads, v.shape[-1])


def _kernel_path(q, k, v, segment_ids, scale, interpret: bool = False, window: int | None = None):
    """``head_major_attention`` for operands as the models hold them: ``q`` scaled (in float32, rounded
    once), then one transpose an array to the kernels' layout and one back."""
    batch, t, heads, _ = q.shape
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    end_to_end = lambda x: x.transpose(2, 0, 1, 3).reshape(x.shape[2], batch * t, x.shape[3])
    attend = head_major_attention(segment_ids, heads, interpret, window)
    out = attend(end_to_end(q), end_to_end(k), end_to_end(v))
    return out.reshape(heads, batch, t, -1).transpose(1, 2, 0, 3)


def head_major_attention(segment_ids, heads: int, interpret: bool = False, window: int | None = None):
    """The kernel path on operands that ARE in the kernels' layout, for ``segment_ids`` (batch, T) of whole
    blocks: ``attend(q, k, v)`` with ``q`` (heads, batch x T, head size) ALREADY SCALED, ``k`` and ``v``
    (kv_heads, batch x T, head size / value head size), the batch's sequences laid end to end ->
    (heads, batch x T, value head size).  The shipped kernel (one call of each of its three kernels a
    batch), built from the mask that is causal inside each sequence (and reaches no further back than
    ``window``), with its three block lists cut to the documents of the step (``_document_block_lists``:
    computed here, before ``attend`` is called).  ``_kernel_path`` feeds it by a scale and transposes,
    ops/attention_edges.py by its passes."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    batch, t = segment_ids.shape
    kernel = _causal_kernel(batch, t, heads, tuple(BLOCK_SIZES.items()), interpret, window)
    seg = segment_ids.reshape(batch * t)
    with_lists = _document_block_lists(kernel, seg, heads)
    return lambda q, k, v: with_lists(q, k, v, splash.SegmentIds(q=seg, kv=seg))


@functools.lru_cache(maxsize=None)
def _causal_kernel(sequences: int, t: int, heads: int, block_sizes: tuple, interpret: bool, window: int | None = None):
    """The shipped kernel for ``sequences`` of ``t`` tokens laid end to end,
    with the static block lists of the mask that is causal inside each (and,
    given a ``window``, reaches no further back than that): a block pair of
    two sequences is in no list, and the library shrinks each grid to the
    widest row of blocks that run (one sequence's; a window's).  Built once a
    shape and window and outside any trace, so that its lists stay arrays
    that ``_follow_documents`` can read while a step is traced."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    class SequencesCausalMask(splash.CausalMask):
        """``k <= q`` and both of one sequence, for the block lists; inside
        the kernel the causal rule alone (``mask_function``), which is the
        same wherever a block runs: a sequence is whole blocks."""

        def __getitem__(self, idx):
            rows, cols = self.q_sequence[idx[0]][:, None], self.q_sequence[idx[1]][None, :]
            return (rows >= cols) & (rows // t == cols // t)

    class SequencesWindowMask(splash.LocalMask):
        """The same with ``q - k < window``: the library's local mask (its
        ``mask_function`` is the rule inside the kernel), cut to one sequence
        for the block lists alone."""

        def __getitem__(self, idx):
            rows, cols = self.q_sequence[idx[0]][:, None], self.q_sequence[idx[1]][None, :]
            return (rows >= cols) & (rows - cols < window) & (rows // t == cols // t)

    assert all(t % b == 0 for b in dict(block_sizes).values())
    n = sequences * t
    mask = SequencesCausalMask((n, n)) if window is None else SequencesWindowMask((n, n), (window - 1, 0), 0)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash.MultiHeadMask([mask] * heads),
            block_sizes=splash.BlockSizes(**dict(block_sizes)), head_shards=1, q_seq_shards=1,
            residual_checkpoint_name=RESIDUALS, interpret=interpret)


def _block_pairs(seg, block_q: int, block_kv: int):
    """For ``segment_ids`` (..., T), on the host (numpy) or on the device:
    ``causal``, numpy bool (query blocks, key blocks), the pairs that hold a
    pair ``k <= q``, and ``shares`` (..., query blocks, key blocks), those of
    them that hold such a pair of one document.

    Documents are contiguous runs, so a key block that ends before a query
    block begins shares a document with it exactly when its last token and
    the query block's first token do."""
    t = seg.shape[-1]
    q_first, k_first = np.arange(0, t, block_q), np.arange(0, t, block_kv)
    q_last, k_last = q_first + block_q - 1, k_first + block_kv - 1
    causal = k_first[None, :] <= q_last[:, None]
    meets_itself = causal & (k_last[None, :] >= q_first[:, None])
    same = seg[..., q_first][..., :, None] == seg[..., k_last][..., None, :]
    return causal, causal & (meets_itself | same)


def _document_block_lists(kernel, seg, heads: int):
    """``kernel`` (a ``SplashAttentionKernel`` built from a static mask) with
    the block lists of its forward, dq and dk/dv kernels cut to the blocks
    that share a document in ``seg`` (tokens,): a step of the grid whose two
    blocks share none is not run and fetches nothing.  ``seg`` may be
    sequences laid end to end whose ids begin again: what two blocks of two
    sequences share is never asked, the static lists do not hold them."""
    blocks = kernel.kwargs["block_sizes"]
    lists = [_follow_documents(info, _block_pairs(seg, bq, bkv)[1], heads, is_dkv) for info, bq, bkv, is_dkv in (
        (kernel.fwd_mask_info, blocks.block_q, blocks.block_kv, False),
        (kernel.dq_mask_info, blocks.block_q_dq, blocks.block_kv_dq, False),
        (kernel.dkv_mask_info, blocks.block_q_dkv, blocks.block_kv_dkv, True))]
    return type(kernel)(*lists, **kernel.kwargs)


def _follow_documents(info, shares, heads: int, is_dkv: bool):
    """One ``MaskInfo`` of the library (one list for all heads), in the
    layout the library built it (entry ``[0, i, j]`` is read by the grid
    step whose inner index is ``j``, or ``i`` in the dk/dv kernel; that axis
    may be shrunk to the steps that run, so where a step runs ``data_next``
    names the key block, or the query block in the dk/dv kernel, that it
    stands for and fetches), with ``block_mask`` zeroed where ``shares``
    (query blocks, key blocks) says the step's two blocks share no document,
    and ``data_next`` of every step that does not run naming the block of the
    next step that does, in the order the grid visits them, so that a step
    left out starts no copy.  Forward and dq visit (head, query block, key
    block): one list serves every head, and after a head's last step comes
    the next head's first.  The dk/dv kernel visits (key block, head, query
    block): after a head's last step of a key block comes the next head's
    first step of the SAME key block, and only after the last head's the
    next key block's, so its lists are by head."""
    static_mask, static_next = np.asarray(info.block_mask)[0], np.asarray(info.data_next)[0]
    runs = _runs(static_mask, static_next, shares, is_dkv)
    block_mask = jnp.where(runs, static_mask, 0).astype(static_mask.dtype)
    in_grid_order = (lambda x: x.T.reshape(-1)) if is_dkv else (lambda x: x.reshape(-1))  # of one head's steps
    n = static_mask.size
    next_run = jax.lax.cummin(jnp.where(in_grid_order(runs), jnp.arange(n), n), reverse=True)
    next_run = jnp.where(next_run == n, next_run[0], next_run)  # after the last: the first
    data_next = jnp.asarray(in_grid_order(static_next))[next_run]
    if not is_dkv:
        return info._replace(block_mask=block_mask[None], data_next=data_next.reshape(1, *static_mask.shape))
    data_next = data_next.reshape(static_mask.shape[::-1]).T
    none_later = jax.lax.cummax(runs.astype(jnp.int8), axis=0, reverse=True) == 0  # in this key block's column
    columns_first = jnp.min(jnp.where(runs, static_next, np.iinfo(static_next.dtype).max), axis=0)  # its diagonal runs
    not_last_head = (np.arange(heads) < heads - 1)[:, None, None]
    data_next = jnp.where(none_later & not_last_head, columns_first, data_next)
    return info._replace(block_mask=jnp.broadcast_to(block_mask, data_next.shape), data_next=data_next)


def _runs(static_mask, static_next, shares, is_dkv: bool):
    """Bool, in the layout of a ``MaskInfo``'s ``block_mask`` and ``data_next``
    (one head's, numpy): the grid steps of the static list whose two blocks
    ``shares`` (query blocks, key blocks) says share a document."""
    rows, cols = np.indices(static_mask.shape)
    q_block, k_block = (static_next, cols) if is_dkv else (rows, static_next)
    return jnp.asarray(static_mask > 0) & shares[q_block, k_block]


def block_pair_counts(segment_ids, block_q: int, block_kv: int) -> tuple[int, int]:
    """On the host, for a batch's ``segment_ids`` (batch, T): the (query
    block, key block) pairs the causal lists run, and how many of them hold
    at least one pair of the same document: what the lists that follow the
    documents run."""
    seg = np.asarray(segment_ids)
    causal, shares = _block_pairs(seg, block_q, block_kv)
    return seg.shape[0] * int(causal.sum()), int(shares.sum())
