"""What the language models share (models/granite_hybrid.py,
models/deepseek_v2.py, models/nemotron_h.py, models/keye_vl2.py): RMSNorm, the
matmul with a weight, the gated SiLU MLP and the squared-ReLU MLP, a Mamba-2
mixer's depthwise convolution, the embedding lookup, the head, the next-token
loss, and what a layer recomputed in the backward pass keeps (``LAYER_KEEPS``).

A matmul's operands are rounded by the CALLER's ``cast`` (its module's
``_operand`` bound to its config): the benchmark's precision controls patch
that one function of a model's module and nothing here.  Norms and the loss
reduce in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.ops import attention, document_conv
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse

# The policy of every model's ``jax.checkpoint(_layer)``: of a layer its input is kept, and of its inside
# what carries one of these names - the attention kernels' output and log-sum-exp (a few hundred MB a step
# against a second run of the forward kernel a layer) and the sparse attention's thresholds.  Where the
# xla lowerings run nothing carries the first name: the input (and the thresholds) alone, as before.
LAYER_KEEPS = jax.checkpoint_policies.save_only_these_names(attention.RESIDUALS, sparse.THRESHOLD)


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def matmul(cast, x, w):
    return jnp.dot(cast(x), cast(w))


def gated_mlp(cast, p, u):
    """``W_down (silu(W_g u) * W_u u)`` with ``p = {gate_up, down}``."""
    gate, up = jnp.split(matmul(cast, u, p["gate_up"]), 2, axis=-1)
    return matmul(cast, jax.nn.silu(gate) * up, p["down"])


def relu2_mlp(cast, p, u):
    """``W_down relu(W_up u)^2`` with ``p = {up, down}``: no gate."""
    return matmul(cast, jnp.square(jax.nn.relu(matmul(cast, u, p["up"]))), p["down"])


# The mixers' depthwise convolution, ``document_conv_silu(x, w, b, segment_ids)`` -> float32: the models look
# it up HERE at call time (the benchmark's mutations patch this name); ops/document_conv.py says which
# lowering runs.
document_conv_silu = document_conv.document_conv_silu


def embed_lookup(table, tokens, dtype, multiplier=None):
    rows = table[tokens]
    return (rows if multiplier is None else multiplier * rows).astype(dtype)


def head_logits(cast, x, table):
    """float32 logits of ``x`` (batch, T, d) over ``table``'s rows (vocabulary, d)."""
    return jnp.einsum("btd,vd->btv", cast(x), cast(table), preferred_element_type=jnp.float32)


def next_token_loss(logits, tokens, segment_ids):
    """Mean cross-entropy of the next token over positions whose next token
    lies in the same document; also the number of such positions."""
    targets = tokens[:, 1:]
    counted = (segment_ids[:, 1:] == segment_ids[:, :-1]).astype(jnp.float32)
    logits = logits[:, :-1].astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    n = jnp.sum(counted)
    return jnp.sum(nll * counted) / jnp.maximum(n, 1.0), n
