"""The depthwise causal convolution in front of a mixer's scan (Mamba-2: Dao & Gu,
arXiv:2405.21060; Gated DeltaNet: arXiv:2412.06464), over packed documents.

    y_t = silu(b + sum over j < taps of w[taps - 1 - j] * x_{t-j})

per channel, where ``x_{t-j}`` counts only if token ``t - j`` exists and carries token
``t``'s segment id: the window does not reach into the previous document.

Precision: ``x`` converted to float32 first; taps, sum and SiLU float32; the result
float32 (the caller rounds it, or does not).

One algorithm, two lowerings; ``lowering`` says which runs, from the backend and the
shapes alone:

- ``xla``: the body below, ``taps`` shifted and masked float32 copies of ``x``
  weighted and summed, differentiated by JAX (each pad, slice and select transposed,
  each tap's gradient a reduction of its own over the tokens).
- ``kernel``: ops/pallas/document_conv.py, a forward and a backward Pallas TPU kernel
  under a ``custom_vjp``, tokens last: a block is read once with the lane tile of
  tokens beside it, shifted by lane rolls in VMEM, and written once; the backward
  recomputes the pre-activation and adds up the taps' and the bias's gradients in
  VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.ops.pallas import document_conv as kernel_lib

KERNEL, XLA = "kernel", "xla"

# MEASURED (v5e-1, my chip run, PR 42: _the kernels and the XLA body ALONE_, bfloat16 in, float32 out, 4 taps, log-normal
# documents, 20 calls timed; the three cells' shapes: granite (1, 8192, 4352), nemo3 (2, 8192, 6144), olmo (1, 8192, 11520).
# ms a call, forward / backward (the backward kernel alone: it recomputes what it needs) / forward + backward.  The bytes a
# call must move are 6 an element forward (x 2 read, y 4 written) and 8 backward (x 2, the cotangent 4, dx 2): 214 / 285
# MB, 604 / 805 MB, 566 / 755 MB; at the chip's 819 GB/s 0.261 / 0.348, 0.737 / 0.983, 0.691 / 0.922 ms):
#                                                   granite                 nemo3                    olmo
#   xla body (tokens first in and out; one
#   program forward, one forward + backward)        1.666 /   -   / 3.226   5.530 /   -   / 10.547   5.206 /   -   / 9.845
#   kernels, TOKENS LAST, blocks (tokens, channels):
#     (2048, 256)  <- kept                          0.396 / 0.711 / 1.107   1.025 / 1.887 / 2.912    0.966 / 1.782 / 2.748
#                     share of the bytes' roof      66% / 49%               72% / 52%                72% / 52%
#     (1024, 256)                                   0.426 / 0.771           1.129 / 2.066            1.062 / 1.951
#     (2048, 128)                                   0.418 / 0.713           1.102 / 1.909            1.037 / 1.800
#     (4096, 128)                                   0.411 / 0.678           1.086 / 1.811            1.031 / 1.709
#     (2048, 512)                                   0.384 / 0.716           1.012 / 1.931            0.970 / 1.839
#     (8192, 64)                                    0.449 / 0.620           1.184 / 1.671            1.121 / 1.554
#     (512, 512)                                    0.504 / 0.890           1.214 / 2.338            1.156 / 2.242
#   (a step runs forward twice and backward once a mixer: 1.503 ms at the kept blocks in granite, 1.518 at (8192, 64), 1.500
#   at (4096, 128): within 1% of each other, so PR 41's choice stands and no further tuning was asked.  On the chip the
#   kernels' values against the body's: y equal to the last bit, dx within one bfloat16 rounding, dw and db to 3e-7 of
#   their largest.)  In the cells' traced runs a call takes 0.352 / 0.651 ms (granite), 0.988 / 1.828 (nemo3), 0.919 /
#   1.716 (olmo): faster than alone.
# ONE MIXER (the models' mixer under jax.checkpoint, forward + recomputed forward + backward, ms; xla body -> kernels; my
# chip run, PR 42): granite 15.02 -> 12.10, nemo3 43.44 -> 36.04, olmo 59.14 -> 50.33; gradient norms equal to 1e-7.
# WHY TOKENS LAST (PR 41's builder's readings; that PR was refused for one lost benchmark pair and its tokens-first code
# was not kept, so these cannot be read again): a first pair over (batch, T, channels) (sublane rolls, the masks a column a
# token; blocks of 1024 tokens x 640 channels at most) ran as fast ALONE (granite 0.408 / 0.663, nemo3 0.968 / 1.588, olmo
# 0.909 / 1.523) and one mixer took 13.32 / 42.65 / 56.40 ms.  XLA lays a mixer out tokens-last on a TPU (in_proj's product
# comes out bf16[1,8192,8512]{1,2,0} for the scan's kernels), so that pair turned the whole mixer tokens-first and bought
# copies: in granite's cell mamba/conv 50.09 -> 18.39 ms but mamba/ssd 30.30 -> 44.43 (the scan's float32 output copied
# {2,1,0} -> {1,2,0} three times a mixer) and in_proj +3.6, the step 435.91 -> 422.33 only; in olmo's conv 63.94 -> 37.97
# but delta_rule +4.8, gate_norm +9.4, in_proj +5.9, the step 587.85 -> 582.95.  A kernel's time alone says nothing: time
# one mixer, or the cell.  What is left around the tokens-last calls: PERF.md section 6.

def lowering(backend: str, seq_len: int, channels: int, taps: int) -> str:
    """``kernel`` where the kernels can run: a TPU backend, a sequence of whole token
    blocks, channels of whole sublane tiles (16 rows of bfloat16), a window no longer
    than the lane tile a block sees of its neighbour; ``xla`` everywhere else (the CPU,
    the tiny presets, a ragged sequence)."""
    whole = seq_len > 0 and seq_len % kernel_lib.TOKEN_BLOCK == 0 and channels > 0 and channels % 16 == 0
    return KERNEL if backend == "tpu" and whole and 1 <= taps <= kernel_lib.LANES + 1 else XLA


def _same_document_shift(x, segment_ids, j: int):
    """``x`` delayed by ``j`` tokens, zero where that token is before the
    sequence or in another document."""
    if j == 0:
        return x
    moved = jnp.pad(x[:, :-j], [(0, 0), (j, 0), (0, 0)])
    same = jnp.pad(segment_ids[:, :-j], [(0, 0), (j, 0)], constant_values=-1) == segment_ids
    return jnp.where(same[..., None], moved, 0)


def document_conv_silu(x, w, b, segment_ids):
    """``silu(conv1d(x) + b)`` float32: depthwise over ``x`` (batch, T,
    channels), causal, ``w`` (taps, channels) with the last tap on the token
    itself, not reaching into the previous document."""
    k = w.shape[0]
    if lowering(jax.default_backend(), x.shape[1], x.shape[2], k) == KERNEL:
        return via_kernels(x, w, b, segment_ids)
    x32 = x.astype(jnp.float32)
    return jax.nn.silu(b + sum(w[k - 1 - j] * _same_document_shift(x32, segment_ids, j) for j in range(k)))


def same_document_bits(segment_ids, taps: int):
    """(batch, 1, T) int32, the kernels' masks: bit j (0 < j < taps) set where token t - j exists and
    carries t's segment id."""
    bits = jnp.zeros(segment_ids.shape, jnp.int32)
    for j in range(1, min(taps, segment_ids.shape[1])):
        same = jnp.pad(segment_ids[:, j:] == segment_ids[:, :-j], [(0, 0), (j, 0)])
        bits = bits | (same.astype(jnp.int32) << j)
    return bits[:, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _tokens_last(x, w, b, same, blocks, interpret):
    """The kernel pair on (batch, channels, T): ops/pallas/document_conv.py::forward, differentiated by
    its ``backward``.  The residuals are the operands: the backward kernel recomputes the pre-activation."""
    return kernel_lib.forward(x, w, b, same, blocks, interpret)


def _tokens_last_fwd(x, w, b, same, blocks, interpret):
    return kernel_lib.forward(x, w, b, same, blocks, interpret), (x, w, b, same)


def _tokens_last_bwd(blocks, interpret, residuals, dy):
    return (*kernel_lib.backward(*residuals, dy, blocks, interpret), None)


_tokens_last.defvjp(_tokens_last_fwd, _tokens_last_bwd)


def via_kernels(x, w, b, segment_ids, blocks=None, interpret=False):
    """The ``kernel`` lowering (``blocks``, ``interpret``: ops/pallas/document_conv.py::forward).  The
    kernels take and give (batch, channels, T); the two transpositions here are layouts to XLA, which
    holds a mixer's activations tokens-last on a TPU."""
    bias = jnp.broadcast_to(jnp.asarray(b, jnp.float32), x.shape[-1:])  # olmo's is the scalar 0.0
    same = same_document_bits(segment_ids, w.shape[0])
    y = _tokens_last(jnp.swapaxes(x, 1, 2), w, bias, same, blocks, interpret)
    return jnp.swapaxes(y, 1, 2)
