"""Granite 4.0-H: a decoder of Mamba-2 mixers with a grouped-query attention
layer in every period, a gated MLP after every mixer, and a tied head.

Equations (ibm-granite/granite-4.0-h-micro ``config.json``, ``model_type``
``granitemoehybrid`` with no experts; ``d`` the hidden size):

- ``x0 = embedding_multiplier * E[tokens]``.
- every layer: ``h = x + r * Mixer(RMSNorm(x))``, ``x' = h + r * MLP(RMSNorm(h))``
  with ``r = residual_multiplier``; ``MLP(u) = W_down (silu(W_g u) * W_u u)``.
- attention layer: grouped-query, no positional encoding, causal and within
  one document, ``softmax(attention_multiplier * q k^T) v`` (ops/attention.py:
  a blocked kernel on a TPU, XLA elsewhere), then ``W_o``.
- mamba layer: ``[z, xBC, dt] = W_in u``; ``xBC = silu(conv1d(xBC) + b)``
  (depthwise, causal, not reaching into the previous document); split into
  ``X`` (heads x head size), ``B``, ``C`` (state size each, one group);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence of
  ``ops/ssd.py`` (a kernel pair on a TPU, XLA elsewhere) plus ``D * X``;
  ``RMSNorm(Y * silu(z)) * w`` over all inner channels; ``W_out``.
- ``logits = RMSNorm(x_L) E^T / logits_scaling``; the loss is the mean
  cross-entropy of the next token over positions whose next token lies in
  the same document.

Plain functions over a parameter tree (no flax): the tree's top level is the
kind of parameter (``embed``, ``mamba``, ``attention``, ``mlp``, ``norms``),
so that the numerics plane's per-group gradient norms (obs/numerics.py) and
a reader of a checkpoint see the model's parts.  Parameters are float32;
activations and matmul operands ``config.dtype`` (bfloat16); norms, softmax,
the scan's decays and state and the loss reduce in float32, in XLA's lowering
and inside the attention and scan kernels alike (ops/attention.py, ops/ssd.py).
Every layer is recomputed in the backward pass: the layers' inputs are kept,
where the attention kernels run their output and log-sum-exp, and where the
device has the room the MLPs' products with ``gate_up``
(``lm_layers.layer_keeps`` decides, ``lm_layers.MLP_GATE_UP`` is their name;
``run_meta`` says what was kept).  Single device: sharding comes with its own
issue.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.models.lm_layers import next_token_loss
from batchai_retinanet_horovod_coco_tpu.ops import attention, document_conv, ssd

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Queries per block of the attention layer where XLA lowers it
    # (ops/attention.py): block i attends keys [0, end of block i), so scores
    # are never (T, T) at once.
    attention_q_block: int = 1024

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def attention_head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "GraniteHybridConfig":
        """From the keys of the published ``config.json``; refuses what this
        model does not compute rather than ignoring it."""
        want = {"hidden_act": "silu", "position_embedding_type": "nope", "tie_word_embeddings": True,
                "normalization_function": "rmsnorm", "mamba_n_groups": 1, "num_local_experts": 0,
                "attention_bias": False, "mamba_proj_bias": False, "mamba_conv_bias": True}
        wrong = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
        if wrong:
            raise ValueError(f"granite_hybrid does not compute {wrong}; it computes {want}")
        layer_types = tuple(hf["layer_types"][: hf["num_hidden_layers"]])
        if len(layer_types) != hf["num_hidden_layers"] or set(layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {hf['layer_types']} for {hf['num_hidden_layers']} layers")
        if hf["mamba_expand"] * hf["hidden_size"] != hf["mamba_n_heads"] * hf["mamba_d_head"]:
            raise ValueError("mamba_expand x hidden_size must equal mamba_n_heads x mamba_d_head")
        if hf.get("shared_intermediate_size", hf["intermediate_size"]) != hf["intermediate_size"]:
            raise ValueError("shared_intermediate_size differs from intermediate_size")
        keys = {f.name for f in dataclasses.fields(cls)} - {"layer_types", "dtype"}
        return cls(layer_types=layer_types, **{**{k: hf[k] for k in keys if k in hf}, **overrides})


# The CPU tests' and ``train.py lm-synthetic``'s default: one period of ten
# layers (local layer 5 the attention layer) at toy widths.
TINY = GraniteHybridConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    layer_types=(MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4,
    num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
    attention_multiplier=0.25, attention_q_block=32,
)

# What is set here and not by the published configuration (the benchmark's
# configuration file lists them under ``assumed``).
INIT_STD = 0.02
A_INIT_RANGE = (1.0, 16.0)
DT_INIT_RANGE = (1e-3, 1e-1)


def init_params(config: GraniteHybridConfig, rng: jax.Array) -> dict:
    d, ff = config.hidden_size, config.intermediate_size
    inner, n, heads = config.mamba_d_inner, config.mamba_d_state, config.mamba_n_heads
    conv_dim = inner + 2 * n
    hd = config.attention_head_dim
    kv = config.num_key_value_heads * hd

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    keys = iter(jax.random.split(rng, 1 + 8 * len(config.layer_types)))
    params: dict = {"embed": {"embedding": normal(next(keys), (config.vocab_size, d))},
                    MAMBA: {}, ATTENTION: {}, "mlp": {}, "norms": {"final": jnp.ones((d,), jnp.float32)}}
    for i, kind in enumerate(config.layer_types):
        name = f"layer_{i}"
        if kind == MAMBA:
            dt = jnp.exp(jax.random.uniform(next(keys), (heads,), jnp.float32,
                                            math.log(DT_INIT_RANGE[0]), math.log(DT_INIT_RANGE[1])))
            params[MAMBA][name] = {
                "in_proj": normal(next(keys), (d, 2 * inner + 2 * n + heads)),
                "conv_w": normal(next(keys), (config.mamba_d_conv, conv_dim)),
                "conv_b": jnp.zeros((conv_dim,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(jax.random.uniform(next(keys), (heads,), jnp.float32, *A_INIT_RANGE)),
                "D": jnp.ones((heads,), jnp.float32),
                "norm_w": jnp.ones((inner,), jnp.float32),
                "out_proj": normal(next(keys), (inner, d)),
            }
        else:
            params[ATTENTION][name] = {
                "q": normal(next(keys), (d, d)), "k": normal(next(keys), (d, kv)),
                "v": normal(next(keys), (d, kv)), "o": normal(next(keys), (d, d)),
            }
        params["mlp"][name] = {"gate_up": normal(next(keys), (d, 2 * ff)), "down": normal(next(keys), (ff, d))}
        params["norms"][name] = {"mixer": jnp.ones((d,), jnp.float32), "mlp": jnp.ones((d,), jnp.float32)}
    return params


_rms_norm = lm_layers.rms_norm


def _operand(config, x):
    """An operand of a matmul with a weight, in ``config.dtype``."""
    return x.astype(config.dtype)


def _cast(config):
    # bound late: the benchmark's control replaces this module's ``_operand``
    return lambda x: _operand(config, x)


def _matmul(config, x, w):
    return lm_layers.matmul(_cast(config), x, w)


def _mamba_mixer(config, p, u, segment_ids):
    inner, n, heads = config.mamba_d_inner, config.mamba_d_state, config.mamba_n_heads
    batch, t, _ = u.shape
    with jax.named_scope("in_proj"):
        z, xbc, dt = jnp.split(_matmul(config, u, p["in_proj"]), [inner, 2 * inner + 2 * n], axis=-1)
    with jax.named_scope("conv"):
        xbc = lm_layers.document_conv_silu(xbc, p["conv_w"], p["conv_b"], segment_ids).astype(config.dtype)
        x, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
        x = x.reshape(batch, t, heads, config.mamba_d_head)
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        y = ssd.ssd_chunked(x, dt, -jnp.exp(p["A_log"]), b, c, segment_ids, config.mamba_chunk_size)
        y = y + p["D"][:, None] * x.astype(jnp.float32)
    with jax.named_scope("gate_norm"):
        y = y.reshape(batch, t, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = _rms_norm(y, p["norm_w"], config.rms_norm_eps).astype(config.dtype)
    with jax.named_scope("out_proj"):
        return _matmul(config, y, p["out_proj"])


def _attention_mixer(config, p, u, segment_ids):
    batch, t, _ = u.shape
    hd = config.attention_head_dim
    q = _matmul(config, u, p["q"]).reshape(batch, t, config.num_attention_heads, hd)
    k = _matmul(config, u, p["k"]).reshape(batch, t, config.num_key_value_heads, hd)
    v = _matmul(config, u, p["v"]).reshape(batch, t, config.num_key_value_heads, hd)
    out = attention.packed_causal_attention(
        q, k, v, segment_ids, config.attention_multiplier, config.attention_q_block)
    return _matmul(config, out.reshape(batch, t, -1), p["o"])


def _mlp(config, p, u):
    return lm_layers.gated_mlp(_cast(config), p, u)


def _layer(config, kind, mixer_params, mlp_params, norms, x, segment_ids):
    r = config.residual_multiplier
    with jax.named_scope(kind):
        mixer = _mamba_mixer if kind == MAMBA else _attention_mixer
        u = _rms_norm(x, norms["mixer"], config.rms_norm_eps)
        h = x + (r * mixer(config, mixer_params, u, segment_ids)).astype(x.dtype)
    with jax.named_scope("mlp"):
        u = _rms_norm(h, norms["mlp"], config.rms_norm_eps)
        return h + (r * _mlp(config, mlp_params, u)).astype(x.dtype)


def _keeps(config, params, bucket) -> lm_layers.Keeps:
    """What the recomputed layers of a step over ``bucket`` (sequences, tokens) keep: every layer ends
    in one gated MLP (``lm_layers.layer_keeps``)."""
    widths = [config.intermediate_size] * len(config.layer_types)
    return lm_layers.keeps_of(widths, params, bucket, config.hidden_size, config.dtype)


def hidden_states(config: GraniteHybridConfig, params: dict, tokens, segment_ids):
    """The last layer's output before the final norm, (batch, T, d)."""
    policy = lm_layers.policy(_keeps(config, params, tokens.shape))
    with jax.named_scope("embed"):
        x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, config.dtype, config.embedding_multiplier)
    for i, kind in enumerate(config.layer_types):
        name = f"layer_{i}"
        layer = jax.checkpoint(_layer, static_argnums=(0, 1), policy=policy)
        x = layer(config, kind, params[kind][name], params["mlp"][name], params["norms"][name], x, segment_ids)
    return x


def logits_of(config: GraniteHybridConfig, params: dict, hidden):
    """float32 logits over the vocabulary held here, from ``hidden_states``."""
    with jax.named_scope("lm_head"):
        x = _rms_norm(hidden, params["norms"]["final"], config.rms_norm_eps)
        return lm_layers.head_logits(_cast(config), x, params["embed"]["embedding"]) / config.logits_scaling


class GraniteHybrid:
    """The model as the train state and the loop hold it: ``init`` gives
    ``{"params": ...}``, ``apply`` the float32 logits."""

    # the STEP_SCOPES (train/step.py) a step of this model enters
    scopes = ("embed", MAMBA, ATTENTION, "mlp", "lm_head", "loss")

    def __init__(self, config: GraniteHybridConfig):
        self.config = config

    def init(self, rng: jax.Array, tokens=None) -> dict:
        del tokens  # the parameters do not depend on the sequence's length
        return {"params": init_params(self.config, rng)}

    def apply(self, variables: dict, tokens, segment_ids, train: bool = False):
        del train  # no dropout, no batch statistics
        params = variables["params"]
        return logits_of(self.config, params, hidden_states(self.config, params, tokens, segment_ids))

    def describe(self) -> str:
        kinds = self.config.layer_types
        return f"granite hybrid, {len(kinds)} layers ({kinds.count(MAMBA)} mamba)"

    def loss(self, params: dict, tokens, segment_ids):
        """``(loss, the step's scalars)`` as the language-model task
        (train/task.py::LMTask) differentiates and logs them."""
        logits = self.apply({"params": params}, tokens, segment_ids, train=True)
        with jax.named_scope("loss"):
            loss, counted = next_token_loss(logits, tokens, segment_ids)
        return loss, {"loss": loss, "tokens_counted": counted, **attention.step_counters(segment_ids)}

    def run_meta(self, bucket) -> dict[str, Any]:
        """Which lowering the step's attention layer (ops/attention.py), its
        mixers' scans (ops/ssd.py) and the convolutions before them
        (ops/document_conv.py) take, and what its recomputed layers keep: static per program."""
        config, backend = self.config, jax.default_backend()
        params = lm_layers.param_shapes(init_params, config)
        return {**attention.run_meta(backend, bucket[1]), **lm_layers.run_meta(_keeps(config, params, bucket)),
                "ssd_lowering": ssd.lowering(backend, bucket[1], config.mamba_chunk_size, config.mamba_n_heads,
                                             config.mamba_d_head, config.mamba_d_state),
                "conv_lowering": document_conv.lowering(
                    backend, bucket[1], config.mamba_d_inner + 2 * config.mamba_d_state, config.mamba_d_conv)}
