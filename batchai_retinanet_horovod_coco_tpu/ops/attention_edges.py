"""What stands between an attention layer's projections and its attention kernels, as ONE pass
over the operands' dtype each way (per-head RMSNorm + rotation by halves + softmax scale + the
head-major layout on the way in; the output gate + the token-major layout on the way out):

    heads_in(x, w, angles, eps, scale)   x (batch, T, heads x size) -> (heads, batch x T, size)
        y[h, n] = round(scale * rotate_n(w * x[n, h] * rsqrt(mean(x[n, h]^2) + eps)))
    heads_out(o, g)                      o (heads, batch x T, size), g (batch, T, heads x size) -> as g
        y[n, h] = round(o[h, n] * sigmoid(g[n, h]))

``rotate_n`` turns elements ``(i, i + size / 2)`` together by ``angles[n, i]``
(``rope.apply_rotary_halves``); ``angles`` None rotates nothing.  The head-major arrays are what the
splash kernels take and give (``attention.head_major_attention``), the sequences of a batch laid end
to end.  For models whose q and k are normalised PER HEAD and rotated by halves (models/afmoe.py; keye
is the other one of the family, ROADMAP S16 e): a norm over the whole projection, interleaved pairs
or a latent cache are other passes, and share no logic with this one.

Written as the models write it (``lm_layers.rms_norm`` -> ``rope.apply_rotary_halves`` ->
``attention._kernel_path``'s scale and transpose; back: transpose -> float32 x sigmoid -> the ``o``
product's cast) the same work is some twenty q-sized float32 arrays a layer through HBM, most of them
made by reverse-mode differentiation of ``astype`` / ``split`` / ``concatenate`` / ``transpose``
(PERF.md section 6, PR 48).  Here each way is a ``custom_vjp`` whose forward and backward are one
Pallas kernel each (ops/pallas/attention_edges.py); the residuals are the operands (the products a
recomputed layer forms anyway, the kernels' output that ``attention.RESIDUALS`` keeps anyway).

``lowering`` says whether a layer can take the passes, from the backend and the shapes alone: a TPU,
the attention lowering ``kernel``, a head of whole lane tiles, a sequence of whole token blocks.
Everywhere else the models run the lines they always ran.

Precision: the operands converted to float32 first; the norm's reduction and ``rsqrt``, its scale,
the angles, cosines and sines, the softmax scale, the gate's sigmoid and the norm scale's gradient
float32, in the written form's order; ONE rounding at the end of a pass, to the operand's dtype
(``config.dtype``).  The written form rounds q three times between the projection and the kernel
(after the norm, after the rotation, after the scale: 128^-0.5 is no power of two); the way out
rounds where the written form does (the kernel's output, the product with the gate).  Closer to the
float32 reference, never further (tests/unit/test_attention_edges.py holds it to that).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.ops import attention
from batchai_retinanet_horovod_coco_tpu.ops.pallas import attention_edges as kernel_lib

KERNEL, XLA = "kernel", "xla"

# MEASURED (v5e-1, my chip runs, PR 48; 16 384 tokens of 32 / 4 heads of 128, bfloat16; MB = 1e6 bytes; the bytes a call must
# move: way in 134.2 read + 134.2 written (+ 16.8 of tables where it rotates), its backward 3 x 134.2 (+ 16.8); way out 2 x
# 134.2 read + 134.2 written, its backward 3 x 134.2 read + 2 x 134.2 written; k's an eighth of q's).
# IN THE STEP (trinity-mini-train-doc16k traced, seed 3048000002, blocks (512, 8); ms a call and its share of 819 GB/s):
#   heads_in  q, rotated      forward 0.455 (76%)   backward 0.694 (74%)        k: 0.078 / 0.101
#   heads_in  q, not rotated  forward 0.405 (81%)   backward 0.614 (80%)        k: 0.052 / 0.079
#   heads_out                 forward 0.593 (83%)   backward 0.994 (82%)
#   a step runs the forward of each twice (the layer is recomputed) and the backward once, in five layers: 19.97 ms of
#   559.18, where the written lines took 65.0 ms outside the cores' scopes, 14.8 inside them and 9.2 unscoped (PERF.md 6).
# ALONE (one jitted call, 30 timed; the written lines alone as XLA compiles them: way in rotated 3.397 forward and 8.117
# forward + backward, not rotated 2.409 and 3.554, way out 2.282 and 3.458; k's calls of 0.2 ms are the dispatch, not the
# kernel, and the timing's noise is 0.01-0.02 ms: k's rows below are ONE kernel, four heads a step):
#   blocks (tokens, heads)    q rotated fwd / bwd   q not rotated     way out fwd / bwd     k rotated
#   (512, 8)   <- kept        0.490 / 0.740         0.434 / 0.651     0.632 / 1.050         0.216 / 0.415
#   (256, 8)                  0.542 / 0.782         0.437 / 0.713     0.631 / 1.042         0.201 / 0.389
#   (1024, 8)                 0.483 / 0.711         0.435 / 0.641     0.621 / 1.034         0.233 / 0.407
#   (512, 4)                  0.526 / 0.746         0.452 / 0.682     0.638 / 1.059         0.218 / 0.413
#   (1024, 4)                 0.491 / 0.711         0.441 / 0.647     0.614 / 1.036         0.216 / 0.409
#   (2048, 4)                 0.483 / 0.689         0.434 / 0.637     -                     0.219 / 0.443
#   (512, 16)                 0.457 / 0.705         0.425 / 0.627     0.613 / 1.019         0.204 / 0.379
#   (256, 32)                 0.470 / 0.672         0.421 / 0.652     0.609 / 1.011         0.213 / 0.394
#   (512, 32)                 0.462 / 0.673         0.425 / 0.621     0.610 / 1.022         0.206 / 0.393
#   All 32 heads a grid step reads 4-9% faster alone: about 0.9 ms a step (0.16%) if it carried over.  It was not run IN the
#   step, and (512, 8) was, in five pairs and two traced runs: kept.  v's layout alone (``head_major``, XLA's transpose) 0.206
#   alone (the dispatch again); in the step its copies are under 0.05 ms a call, so it has no kernel.
# THE SAME ``custom_vjp`` PAIR WITH ``jax.numpy`` BODIES (ISSUE 48's step b; the same seed, traced): ``train_step.device_ms``
# 622.27 -> 620.63 where the kernels read 559.18: XLA writes the float32 q-sized copies again around a hand-written backward
# (12 float32 fusions and 10 float32 copies of 268 MB in the compiled step, 17.7 GB a step against the lines' 19.7 and the
# kernels' 7.65).  The layout is the cost, and only a kernel that writes head-major slices itself avoids it.


def lowering(backend: str, seq_len: int, head_dim: int) -> str:
    """``kernel`` where the passes can run and have somewhere to go: the attention kernels run
    (``attention.lowering``: a TPU backend, a sequence of whole attention blocks), the sequence is whole
    token blocks of the passes and a head whole lane tiles; ``xla`` (the models' written lines) everywhere
    else (the CPU, the tiny presets' heads of 16, a ragged sequence)."""
    whole = seq_len % kernel_lib.TOKEN_BLOCK == 0 and head_dim % kernel_lib.LANES == 0
    return KERNEL if attention.lowering(backend, seq_len) == attention.KERNEL and whole else XLA


def rotation_tables(angles):
    """``angles`` (batch, T, size / 2) float32 -> ``[cos, cos]`` and ``[-sin, sin]``, (batch x T, size)
    float32 each: ``rotate(n) = n * [cos, cos] + roll(n, size / 2) * [-sin, sin]``."""
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    flat = lambda a, b: jnp.concatenate([a, b], axis=-1).reshape(-1, 2 * angles.shape[-1])
    return flat(cos, cos), flat(-sin, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _heads_in(x, w, tables, eps, scale, blocks, interpret):
    return kernel_lib.heads_in_fwd(x, w, tables, eps, scale, blocks, interpret)


def _heads_in_fwd(x, w, tables, eps, scale, blocks, interpret):
    return kernel_lib.heads_in_fwd(x, w, tables, eps, scale, blocks, interpret), (x, w, tables)


def _heads_in_bwd(eps, scale, blocks, interpret, residuals, dy):
    x, w, tables = residuals
    dx, dw = kernel_lib.heads_in_bwd(x, w, tables, dy, eps, scale, blocks, interpret)
    return dx, dw.astype(w.dtype), None  # positions carry no gradient


_heads_in.defvjp(_heads_in_fwd, _heads_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _heads_out(o, g, blocks, interpret):
    return kernel_lib.heads_out_fwd(o, g, blocks, interpret)


def _heads_out_fwd(o, g, blocks, interpret):
    return kernel_lib.heads_out_fwd(o, g, blocks, interpret), (o, g)


def _heads_out_bwd(blocks, interpret, residuals, dy):
    return tuple(kernel_lib.heads_out_bwd(*residuals, dy, blocks, interpret))


_heads_out.defvjp(_heads_out_fwd, _heads_out_bwd)


def heads_in(x, w, angles, eps: float, scale: float, blocks=None, interpret: bool = False):
    """``x`` (batch, T, heads x size), a projection's product; ``w`` (size,) the per-head norm's scale;
    ``angles`` (batch, T, size / 2) float32 or None -> (heads, batch x T, size) in ``x``'s dtype: every
    head normalised, rotated and times ``scale`` (the softmax scale for q, 1.0 for k), head-major.
    ``blocks``, ``interpret``: ops/pallas/attention_edges.py::heads_in_fwd."""
    batch, t, width = x.shape
    tables = () if angles is None else rotation_tables(angles)
    return _heads_in(x.reshape(batch * t, width), w, tables, eps, scale, blocks, interpret)


def head_major(x, heads: int):
    """``x`` (batch, T, heads x size) -> (heads, batch x T, size): the layout alone (v).  XLA's transpose: 16 MB
    at trinity's four key-value heads, MEASURED above."""
    batch, t, width = x.shape
    return x.reshape(batch * t, heads, width // heads).transpose(1, 0, 2)


def heads_out(o, g, blocks=None, interpret: bool = False):
    """``o`` (heads, batch x T, size), the attention kernels' output; ``g`` (batch, T, heads x size), the
    gate's product -> ``o * sigmoid(float32(g))`` (batch, T, heads x size) in ``g``'s dtype, the ``o``
    product's operand."""
    return _heads_out(o, g.reshape(-1, g.shape[-1]), blocks, interpret).reshape(g.shape)
