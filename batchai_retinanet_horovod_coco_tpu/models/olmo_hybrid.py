"""Olmo-Hybrid: a decoder whose layers are Gated DeltaNet linear-attention
mixers, three of every four, beside full multi-head attention, a gated MLP
after every mixer, and an untied head.

Equations (allenai/Olmo-Hybrid-7B ``config.json``, ``model_type`` ``olmo_hybrid``;
``d`` the hidden size, ``seg`` a token's document; what the published keys do not
say is the benchmark configuration's ``assumed``):

- ``x0 = E[tokens]``; every layer, both kinds, normalises a sublayer's OUTPUT
  before the residual add (the Olmo 2 / Olmo 3 convention):
  ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``,
  ``MLP(h) = W_down (silu(W_gate h) * (W_up h))``, no bias.
- ``linear_attention`` (scope ``gdn``), per head of ``linear_num_value_heads``
  (key size ``K``, value size ``V``):
  ``[q, k, v, z, da, db] = W_in x`` (scope ``in_proj``);
  ``q, k, v = silu(conv(q)), silu(conv(k)), silu(conv(v))`` (scope ``conv``:
  depthwise, causal, ``linear_conv_kernel_dim`` taps, no bias, not reaching into the
  previous document); ``q_h <- q_h / ||q_h||_2 * K^-1/2``, ``k_h <- k_h / ||k_h||_2``;
  ``b = 2 sigmoid(db)`` (the 2 is ``linear_allow_neg_eigval``: the eigenvalues of
  ``I - b k k^T`` lie in [-1, 1]), ``a = exp(-exp(A_log) softplus(da + dt_bias))``;
  ``S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T``, ``S = 0`` before a
  document's first token, ``o_t = S_t q_t`` (scope ``delta_rule``: ops/delta_rule.py,
  a kernel pair on a TPU, XLA elsewhere);
  ``RMSNorm_V(o_h) * silu(z_h)`` (scope ``gate_norm``); ``W_o`` (scope ``out_proj``).
- ``full_attention`` (scope ``attention``): ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)``
  (over the WHOLE projection, before the split into heads of ``d / heads``),
  ``v = W_v x``; ``rope_theta`` None applies no rotation, a number turns q and k by
  the token's position inside its document (ops/rope.py); causal softmax attention
  inside a document, scale ``head size^-1/2`` (ops/attention.py); ``W_o``.
- ``logits = RMSNorm(x_L) H^T`` (``H`` the untied head's rows held here); the loss is
  the mean cross-entropy of the next token over positions whose next token lies in
  the same document.

Plain functions over a parameter tree as models/granite_hybrid.py: the top level
is the kind of parameter (``embed``, ``gdn``, ``attention``, ``mlp``, ``norms``,
``head``).  float32 parameters; ``config.dtype`` (bfloat16) activations and matmul
operands; float32 norms, softmax, the L2 norms, ``a``, ``b``, the cumulative
log-decays, the triangular system's solution, the carried state, logits and loss.
Every layer is recomputed in the backward pass: the layers' inputs are kept,
where the attention kernels run their output and log-sum-exp, and where the device
has the room the MLPs' products with ``gate_up`` (``lm_layers.layer_keeps`` decides,
``lm_layers.MLP_GATE_UP`` is their name, ``run_meta`` says what was kept: at the
published widths a v5e has not); the delta rule's kernels name nothing, so their forward
runs again (the states they save live inside one layer's backward pass).
Single device.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.ops import attention, delta_rule, document_conv, rope

LINEAR, FULL = "linear_attention", "full_attention"
SCOPE = {LINEAR: "gdn", FULL: "attention"}  # a kind's scope (train/step.py::STEP_SCOPES) and parameter group
ALPHA_MEAN, BETA_MEAN, STATE_NORM_MAX = "gdn/alpha_mean", "gdn/beta_mean", "gdn/state_norm_max"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_theta: float | None = None
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention_q_block: int = 1024  # as GraniteHybridConfig's; the xla lowering's blocks of queries
    # tokens of a chunk of the delta rule (ops/delta_rule.py); no published key: the builder's, by chip readings
    delta_rule_chunk: int = 128

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "OlmoHybridConfig":
        """From the keys of the published ``config.json``; refuses what this
        model does not compute rather than ignoring it."""
        want = {"hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False}
        wrong = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
        if hf["linear_num_key_heads"] != hf["linear_num_value_heads"]:
            wrong["linear_num_key_heads"] = hf["linear_num_key_heads"]
        if hf.get("head_dim") not in (None, hf["hidden_size"] // hf["num_attention_heads"]):
            wrong["head_dim"] = hf["head_dim"]
        if wrong:
            raise ValueError(f"olmo_hybrid does not compute {wrong}; it computes {want} with as many linear key heads "
                             "as value heads and attention heads of hidden_size / num_attention_heads")
        layer_types = tuple(hf["layer_types"][: hf["num_hidden_layers"]])
        if len(layer_types) != hf["num_hidden_layers"] or set(layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types {hf['layer_types']} for {hf['num_hidden_layers']} layers")
        if hf["hidden_size"] % hf["num_attention_heads"] or hf["num_attention_heads"] % hf["num_key_value_heads"]:
            raise ValueError("hidden_size / num_attention_heads / num_key_value_heads must divide")
        keys = {f.name for f in dataclasses.fields(cls)} - {"layer_types", "dtype", "rope_theta"}
        # read as published: ``rope_parameters.rope_theta`` null is no rotation, a number a rotation
        theta = (hf.get("rope_parameters") or {}).get("rope_theta")
        return cls(layer_types=layer_types, rope_theta=theta, **{**{k: hf[k] for k in keys if k in hf}, **overrides})


# The CPU tests' and ``train.py lm-synthetic --model tiny-olmo``'s: one period at toy widths.
TINY = OlmoHybridConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128, layer_types=(LINEAR,) * 3 + (FULL,),
    num_attention_heads=4, num_key_value_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, attention_q_block=32, delta_rule_chunk=8,
)

# What is set here and not by the published configuration (the benchmark's
# configuration file lists them under ``assumed``).
INIT_STD = 0.02
A_INIT_RANGE = (1.0, 16.0)
DT_INIT_RANGE = (1e-3, 1e-1)
L2_EPS = 1e-6


def init_params(config: OlmoHybridConfig, rng: jax.Array) -> dict:
    d, ff, heads = config.hidden_size, config.intermediate_size, config.linear_num_value_heads
    kd, vd = config.linear_key_dim, config.linear_value_dim
    kv = config.num_key_value_heads * config.head_dim

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    keys = iter(jax.random.split(rng, 2 + 8 * len(config.layer_types)))
    params: dict = {"embed": {"embedding": normal(next(keys), (config.vocab_size, d))},
                    "gdn": {}, "attention": {}, "mlp": {}, "norms": {"final": jnp.ones((d,), jnp.float32)},
                    "head": {"rows": normal(next(keys), (config.vocab_size, d))}}
    for i, kind in enumerate(config.layer_types):
        name = f"layer_{i}"
        if kind == LINEAR:
            dt = jnp.exp(jax.random.uniform(next(keys), (heads,), jnp.float32,
                                            math.log(DT_INIT_RANGE[0]), math.log(DT_INIT_RANGE[1])))
            params["gdn"][name] = {
                "in_proj": normal(next(keys), (d, 2 * kd + 2 * vd + 2 * heads)),  # q, k, v, z, da, db
                "conv_w": normal(next(keys), (config.linear_conv_kernel_dim, 2 * kd + vd)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(jax.random.uniform(next(keys), (heads,), jnp.float32, *A_INIT_RANGE)),
                "norm_w": jnp.ones((config.linear_value_head_dim,), jnp.float32),
                "out_proj": normal(next(keys), (vd, d)),
            }
        else:
            params["attention"][name] = {
                "q": normal(next(keys), (d, d)), "k": normal(next(keys), (d, kv)),
                "v": normal(next(keys), (d, kv)), "o": normal(next(keys), (d, d)),
                "q_norm": jnp.ones((d,), jnp.float32), "k_norm": jnp.ones((kv,), jnp.float32),
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


def _state_operand(config, x):
    """A key or a value as the delta rule takes it (the benchmark's STATE control
    replaces this and ``ops/pallas/delta_rule.py::_state_operand``)."""
    return x.astype(config.dtype)


def _l2_normalised(x, scale: float = 1.0):
    x32 = x.astype(jnp.float32)
    return x32 * (scale * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + L2_EPS))


def _gdn_mixer(config, p, u, segment_ids):
    """-> (the mixer's output, (mean a, mean b, the largest norm of a state))."""
    heads, kd, vd = config.linear_num_value_heads, config.linear_key_dim, config.linear_value_dim
    batch, t, _ = u.shape
    with jax.named_scope("in_proj"):
        qkv, z, da, db = jnp.split(_matmul(config, u, p["in_proj"]), [2 * kd + vd, 2 * kd + 2 * vd, 2 * kd + 2 * vd + heads],
                                   axis=-1)
    with jax.named_scope("conv"):
        qkv = lm_layers.document_conv_silu(qkv, p["conv_w"], 0.0, segment_ids)  # float32
        q, k, v = jnp.split(qkv, [kd, 2 * kd], axis=-1)
        by_head = lambda x: x.reshape(batch, t, heads, -1)
        q = _l2_normalised(by_head(q), config.linear_key_head_dim ** -0.5).astype(config.dtype)
        k = _state_operand(config, _l2_normalised(by_head(k)))
        v = _state_operand(config, by_head(v))
    with jax.named_scope("delta_rule"):
        b = (2.0 if config.linear_allow_neg_eigval else 1.0) * jax.nn.sigmoid(db.astype(jnp.float32))
        log_a = -jnp.exp(p["A_log"]) * jax.nn.softplus(da.astype(jnp.float32) + p["dt_bias"])
        o, state_norm = delta_rule.gated_delta_rule(q, k, v, log_a, b, segment_ids, config.delta_rule_chunk)
        counters = (jnp.mean(jnp.exp(log_a)), jnp.mean(b), state_norm)
    with jax.named_scope("gate_norm"):
        o = _rms_norm(o, p["norm_w"], config.rms_norm_eps) * jax.nn.silu(by_head(z).astype(jnp.float32))
        o = o.reshape(batch, t, vd).astype(config.dtype)
    with jax.named_scope("out_proj"):
        return _matmul(config, o, p["out_proj"]), jax.lax.stop_gradient(counters)


def _attention_mixer(config, p, u, segment_ids):
    batch, t, _ = u.shape
    hd = config.head_dim
    q = _rms_norm(_matmul(config, u, p["q"]), p["q_norm"], config.rms_norm_eps)
    k = _rms_norm(_matmul(config, u, p["k"]), p["k_norm"], config.rms_norm_eps)
    q = q.reshape(batch, t, config.num_attention_heads, hd)
    k = k.reshape(batch, t, config.num_key_value_heads, hd)
    v = _matmul(config, u, p["v"]).reshape(batch, t, config.num_key_value_heads, hd)
    if config.rope_theta is not None:
        positions = rope.document_positions(segment_ids)
        inv_freq = 1.0 / config.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        q, k = rope.apply_rotary(q, positions, inv_freq), rope.apply_rotary(k, positions, inv_freq)
    out = attention.packed_causal_attention(q, k, v, segment_ids, hd ** -0.5, config.attention_q_block)
    return _matmul(config, out.reshape(batch, t, -1), p["o"])


def _layer(config, kind, mixer_params, mlp_params, norms, x, segment_ids):
    """-> (the layer's output, the delta rule's counters or nothing)."""
    with jax.named_scope(SCOPE[kind]):
        if kind == LINEAR:
            mixed, counters = _gdn_mixer(config, mixer_params, x, segment_ids)
        else:
            mixed, counters = _attention_mixer(config, mixer_params, x, segment_ids), None
        h = x + _rms_norm(mixed, norms["mixer"], config.rms_norm_eps).astype(x.dtype)
    with jax.named_scope("mlp"):
        out = lm_layers.gated_mlp(_cast(config), mlp_params, h)
        return h + _rms_norm(out, norms["mlp"], config.rms_norm_eps).astype(x.dtype), counters


def _keeps(config, params, bucket) -> lm_layers.Keeps:
    """What the recomputed layers of a step over ``bucket`` (sequences, tokens) keep: every layer ends
    in one gated MLP (``lm_layers.layer_keeps``)."""
    widths = [config.intermediate_size] * len(config.layer_types)
    return lm_layers.keeps_of(widths, params, bucket, config.hidden_size, config.dtype)


def hidden_states(config: OlmoHybridConfig, params: dict, tokens, segment_ids):
    """``(x, counters)``: the last layer's output before the final norm (batch, T,
    d), and the delta rule's three counters over the linear-attention layers."""
    with jax.named_scope("embed"):
        x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, config.dtype)
    policy = lm_layers.policy(_keeps(config, params, tokens.shape))
    per_layer = []
    for i, kind in enumerate(config.layer_types):
        name = f"layer_{i}"
        layer = jax.checkpoint(_layer, static_argnums=(0, 1), policy=policy)
        x, counters = layer(config, kind, params[SCOPE[kind]][name], params["mlp"][name], params["norms"][name], x,
                            segment_ids)
        if kind == LINEAR:
            per_layer.append(counters)
    if not per_layer:
        return x, {}
    alpha, beta, norm = (jnp.stack(c) for c in zip(*per_layer))
    return x, {ALPHA_MEAN: jnp.mean(alpha), BETA_MEAN: jnp.mean(beta), STATE_NORM_MAX: jnp.max(norm)}


def logits_of(config: OlmoHybridConfig, params: dict, hidden):
    """float32 logits over the rows of the head held here."""
    with jax.named_scope("lm_head"):
        x = _rms_norm(hidden, params["norms"]["final"], config.rms_norm_eps)
        return lm_layers.head_logits(_cast(config), x, params["head"]["rows"])


class OlmoHybrid:
    """The model as the train state and the loop hold it (as
    models/granite_hybrid.py::GraniteHybrid)."""

    # the STEP_SCOPES (train/step.py, with what lies beneath each) a step of this model enters
    scopes = ("embed", "gdn", "attention", "mlp", "lm_head", "loss")

    def __init__(self, config: OlmoHybridConfig):
        self.config = config

    def init(self, rng: jax.Array, tokens=None) -> dict:
        del tokens  # the parameters do not depend on the sequence's length
        return {"params": init_params(self.config, rng)}

    def apply(self, variables: dict, tokens, segment_ids, train: bool = False):
        del train  # no dropout, no batch statistics
        params = variables["params"]
        return logits_of(self.config, params, hidden_states(self.config, params, tokens, segment_ids)[0])

    def describe(self) -> str:
        kinds = self.config.layer_types
        return f"olmo hybrid, {len(kinds)} layers ({kinds.count(LINEAR)} gated delta rule)"

    def loss(self, params: dict, tokens, segment_ids):
        """``(loss, the step's scalars)`` as the language-model task
        (train/task.py::LMTask) differentiates and logs them: the next-token
        cross-entropy and the delta rule's counters."""
        hidden, counters = hidden_states(self.config, params, tokens, segment_ids)
        logits = logits_of(self.config, params, hidden)
        with jax.named_scope("loss"):
            loss, counted = lm_layers.next_token_loss(logits, tokens, segment_ids)
        return loss, {"loss": loss, "tokens_counted": counted, **counters, **attention.step_counters(segment_ids)}

    def run_meta(self, bucket) -> dict[str, Any]:
        """Which lowering the step's attention layers (ops/attention.py), its delta
        rules (ops/delta_rule.py) and the convolutions before them
        (ops/document_conv.py) take, the delta rule's chunk, and what its recomputed layers
        keep: static per program."""
        config, backend = self.config, jax.default_backend()
        params = lm_layers.param_shapes(init_params, config)
        return {**attention.run_meta(backend, bucket[1]), **lm_layers.run_meta(_keeps(config, params, bucket)),
                "delta_rule_lowering": delta_rule.lowering(
                    backend, bucket[1], config.delta_rule_chunk, config.linear_num_value_heads,
                    config.linear_key_head_dim, config.linear_value_head_dim),
                "delta_rule_chunk": config.delta_rule_chunk,
                "conv_lowering": document_conv.lowering(
                    backend, bucket[1], 2 * config.linear_key_dim + config.linear_value_dim,
                    config.linear_conv_kernel_dim)}
