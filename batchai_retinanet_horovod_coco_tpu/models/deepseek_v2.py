"""DeepSeek-V2: a decoder of latent-attention layers (MLA) whose feed-forward
is a dense gated MLP in the leading layers and routed plus shared experts in
every other, with an untied head.  This chip may hold a SHARE of each
layer's routed experts (``experts_held``): the router stays whole.

Equations (deepseek-ai/DeepSeek-V2-Lite ``config.json`` and the modelling
code published beside it, ``model_type`` ``deepseek_v2``; ``d`` the hidden
size, ``q_lora_rank`` null):

- ``x0 = E[tokens]``; every layer ``h = x + Attn(RMSNorm(x))``,
  ``x' = h + F(RMSNorm(h))``.
- ``Attn`` (scope ``mla``): ``q = u W_q`` -> heads x (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``); ``u W_kva`` -> ``c`` (``kv_lora_rank``) | ``k_pe``
  (one rotary key for all heads); ``c = RMSNorm(c)``; ``c W_kvb`` -> heads x
  (``k_nope`` | ``v``).  Rotary on ``q_pe`` and ``k_pe`` by the position of
  the token INSIDE ITS OWN DOCUMENT, YaRN's frequencies (ops/rope.py).
  ``softmax(scale * [q_nope | q_pe] [k_nope | k_pe]^T) v`` causal and within
  one document (ops/attention.py: the value head is narrower than the
  query/key head), ``scale = (nope + rope)^-1/2 * m^2``, ``m`` YaRN's
  ``mscale_all_dim`` term; then ``W_o``.  The training path materialises k
  and v per head: no weight absorption and no latent cache (serving's).
- ``F`` in the first ``first_k_dense_replace`` layers (scope ``dense_mlp``):
  ``W_down (silu(W_g u) * W_u u)`` of width ``intermediate_size``.
- ``F`` elsewhere (scope ``moe``): ``s = softmax_float32(u W_gate)`` over
  ALL ``experts_total``; the ``num_experts_per_tok`` largest, weights those
  scores as they are (``norm_topk_prob`` false) x ``routed_scaling_factor``;
  the sum over the picked experts HELD here of ``s_e E_e(u)`` (ops/moe.py),
  plus one shared gated MLP of width ``n_shared_experts x
  moe_intermediate_size``.
- the balance loss (``seq_aux``): per expert layer and sequence
  ``alpha sum_i f_i P_i`` over all experts, summed over the layers, added to
  the loss.
- ``logits = RMSNorm(x_L) H^T`` (``H`` the untied head's rows held here).

Plain functions over a parameter tree as models/granite_hybrid.py: the top
level is the kind of parameter (``embed``, ``attention``, ``dense_mlp``,
``router``, ``experts``, ``shared``, ``norms``, ``head``).  float32
parameters; ``config.dtype`` (bfloat16) activations and matmul operands;
float32 norms, router, softmax, rotary angles and loss.  Every layer is
recomputed in the backward pass from its input; where the attention kernels
run their output and log-sum-exp are kept too, and where the device has the
room the dense and the shared MLPs' products with ``gate_up``
(``lm_layers.layer_keeps`` decides, ``lm_layers.MLP_GATE_UP`` is their name;
``run_meta`` says what was kept).
Single device.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.ops import attention, moe, rope


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    experts_total: int  # the router's width: the model's routed experts
    experts_held: tuple[int, ...]  # the ids of those this chip computes
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    aux_loss_alpha: float = 0.001
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    dtype: Any = jnp.bfloat16
    attention_q_block: int = 1024  # as GraniteHybridConfig's

    @property
    def softmax_scale(self) -> float:
        m = rope.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def rotary_scale(self) -> float:
        """What the cosines and sines are multiplied by (1 as published)."""
        return (rope.yarn_mscale(self.rope_factor, self.rope_mscale)
                / rope.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "DeepseekV2Config":
        """From the keys of the published ``config.json``; refuses what this
        model does not compute rather than ignoring it.  ``n_routed_experts``
        counts the experts HELD; a cut configuration adds
        ``n_routed_experts_total`` (the router's width) and ``experts_held``
        (their ids), without which all are held."""
        want = {"hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
                "topk_group": 1, "moe_layer_freq": 1, "norm_topk_prob": False, "seq_aux": True,
                "q_lora_rank": None, "attention_bias": False, "tie_word_embeddings": False}
        wrong = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
        scaling = hf.get("rope_scaling") or {}
        if scaling.get("type") != "yarn":
            wrong["rope_scaling.type"] = scaling.get("type")
        if hf["num_key_value_heads"] != hf["num_attention_heads"]:
            wrong["num_key_value_heads"] = hf["num_key_value_heads"]
        if wrong:
            raise ValueError(f"deepseek_v2 does not compute {wrong}; it computes {want} with yarn rotary "
                             "positions and as many key/value heads as query heads")
        held = tuple(hf.get("experts_held", range(hf["n_routed_experts"])))
        total = hf.get("n_routed_experts_total", hf["n_routed_experts"])
        if len(held) != hf["n_routed_experts"] or len(set(held)) != len(held) or not all(0 <= e < total for e in held):
            raise ValueError(f"experts_held {held} for n_routed_experts {hf['n_routed_experts']} of {total}")
        keys = {f.name for f in dataclasses.fields(cls)} - {"experts_total", "experts_held", "dtype"}
        given = {k: hf[k] for k in keys if k in hf}
        given.update(rope_factor=scaling["factor"], rope_original_positions=scaling["original_max_position_embeddings"],
                     rope_beta_fast=scaling["beta_fast"], rope_beta_slow=scaling["beta_slow"],
                     rope_mscale=scaling["mscale"], rope_mscale_all_dim=scaling["mscale_all_dim"])
        return cls(experts_total=total, experts_held=held, **{**given, **overrides})


# The CPU tests' and ``train.py lm-synthetic --model tiny-moe``'s: one dense
# and two expert layers at toy widths, 4 of 16 experts held, 3 a token.
TINY = DeepseekV2Config(
    vocab_size=128, hidden_size=64, intermediate_size=160, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    experts_total=16, experts_held=(0, 1, 2, 3), n_shared_experts=2, num_experts_per_tok=3,
    rope_original_positions=16, attention_q_block=32,
)

INIT_STD = 0.02  # not given by the published configuration's catalog copy: ``assumed`` in the benchmark's file

def _is_dense(config: DeepseekV2Config, i: int) -> bool:
    return i < config.first_k_dense_replace


def init_params(config: DeepseekV2Config, rng: jax.Array) -> dict:
    d, heads = config.hidden_size, config.num_attention_heads
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    width, held = config.moe_intermediate_size, len(config.experts_held)

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = iter(jax.random.split(rng, 2 + 11 * config.num_hidden_layers))
    params: dict = {"embed": {"embedding": normal(next(keys), (config.vocab_size, d))},
                    "attention": {}, "dense_mlp": {}, "router": {}, "experts": {}, "shared": {},
                    "norms": {"final": ones(d)}, "head": {"rows": normal(next(keys), (config.vocab_size, d))}}
    for i in range(config.num_hidden_layers):
        name = f"layer_{i}"
        params["attention"][name] = {
            "q": normal(next(keys), (d, heads * qk)),
            "kv_a": normal(next(keys), (d, config.kv_lora_rank + config.qk_rope_head_dim)),
            "kv_a_norm": ones(config.kv_lora_rank),
            "kv_b": normal(next(keys), (config.kv_lora_rank, heads * (config.qk_nope_head_dim + config.v_head_dim))),
            "o": normal(next(keys), (heads * config.v_head_dim, d)),
        }
        params["norms"][name] = {"attention": ones(d), "mlp": ones(d)}
        if _is_dense(config, i):
            ff = config.intermediate_size
            params["dense_mlp"][name] = {"gate_up": normal(next(keys), (d, 2 * ff)), "down": normal(next(keys), (ff, d))}
            continue
        shared = config.n_shared_experts * width
        params["router"][name] = {"gate": normal(next(keys), (d, config.experts_total))}
        params["experts"][name] = {"gate_up": normal(next(keys), (held, d, 2 * width)),
                                   "down": normal(next(keys), (held, width, d))}
        params["shared"][name] = {"gate_up": normal(next(keys), (d, 2 * shared)), "down": normal(next(keys), (shared, d))}
    return params


def _operand(config, x):
    """An operand of a matmul with a weight, in ``config.dtype``."""
    return x.astype(config.dtype)


def _cast(config):
    # bound late: the benchmark's control replaces this module's ``_operand``
    return lambda x: _operand(config, x)


def _matmul(config, x, w):
    return lm_layers.matmul(_cast(config), x, w)


def _mla(config, p, u, segment_ids, positions):
    batch, t, _ = u.shape
    heads, nope, pe = config.num_attention_heads, config.qk_nope_head_dim, config.qk_rope_head_dim
    with jax.named_scope("q_proj"):
        q_nope, q_pe = jnp.split(_matmul(config, u, p["q"]).reshape(batch, t, heads, nope + pe), [nope], axis=-1)
    with jax.named_scope("kv_a"):
        c, k_pe = jnp.split(_matmul(config, u, p["kv_a"]), [config.kv_lora_rank], axis=-1)
        c = lm_layers.rms_norm(c, p["kv_a_norm"], config.rms_norm_eps)
    with jax.named_scope("kv_b"):
        k_nope, v = jnp.split(_matmul(config, c, p["kv_b"]).reshape(batch, t, heads, nope + config.v_head_dim),
                              [nope], axis=-1)
    with jax.named_scope("rope"):
        inv_freq = rope.yarn_inv_freq(pe, config.rope_theta, config.rope_factor, config.rope_original_positions,
                                      config.rope_beta_fast, config.rope_beta_slow)
        q_pe = rope.apply_rotary(q_pe, positions, inv_freq, config.rotary_scale)
        k_pe = rope.apply_rotary(k_pe[:, :, None, :], positions, inv_freq, config.rotary_scale)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (batch, t, heads, pe))], axis=-1)
    with jax.named_scope("core"):
        out = attention.packed_causal_attention(q, k, v, segment_ids, config.softmax_scale, config.attention_q_block)
    with jax.named_scope("o_proj"):
        return _matmul(config, out.reshape(batch, t, -1), p["o"])


def _moe_lowering(config, batch: int, t: int) -> str:
    return moe.lowering(jax.default_backend(), batch * t * config.num_experts_per_tok, config.hidden_size,
                        config.moe_intermediate_size)


def _moe(config, router, experts, shared, u):
    """-> (F(u) in ``u``'s dtype, (the balance loss without its coefficient,
    the rows routed here by held expert, the picks (batch, T, k)))."""
    batch, t, _ = u.shape
    k = config.num_experts_per_tok
    routed, routing, plan = moe.expert_layer(
        u, router["gate"], _operand(config, experts["gate_up"]), _operand(config, experts["down"]),
        config.experts_held, k, _moe_lowering(config, batch, t))
    with jax.named_scope("shared"):
        out = config.routed_scaling_factor * routed + lm_layers.gated_mlp(_cast(config), shared, u).astype(jnp.float32)
    with jax.named_scope("aux"):
        picks = routing.picks.reshape(batch, t, k)
        balance = moe.sequence_balance_loss(routing.scores.reshape(batch, t, -1), picks, k)
    return out.astype(u.dtype), (balance, plan.group_sizes, picks)


def _layer(config, dense: bool, attn_p, mlp_p, norms, x, segment_ids, positions):
    with jax.named_scope("mla"):
        u = lm_layers.rms_norm(x, norms["attention"], config.rms_norm_eps)
        h = x + _mla(config, attn_p, u, segment_ids, positions).astype(x.dtype)
    if dense:
        with jax.named_scope("dense_mlp"):
            u = lm_layers.rms_norm(h, norms["mlp"], config.rms_norm_eps)
            return h + lm_layers.gated_mlp(_cast(config), mlp_p, u).astype(x.dtype), None
    with jax.named_scope("moe"):
        u = lm_layers.rms_norm(h, norms["mlp"], config.rms_norm_eps)
        f, routed = _moe(config, *mlp_p, u)
        return h + f, routed


def _keeps(config, params, bucket) -> lm_layers.Keeps:
    """What the recomputed layers of a step over ``bucket`` (sequences, tokens) keep: a dense layer's
    gated MLP, an expert layer's shared one (``lm_layers.layer_keeps``)."""
    widths = [config.intermediate_size if _is_dense(config, i) else config.n_shared_experts * config.moe_intermediate_size
              for i in range(config.num_hidden_layers)]
    return lm_layers.keeps_of(widths, params, bucket, config.hidden_size, config.dtype)


def hidden_states(config: DeepseekV2Config, params: dict, tokens, segment_ids):
    """``(x, balance, rows, picks)``: the last layer's output before the
    final norm (batch, T, d); the expert layers' balance losses summed,
    without the coefficient; the rows routed here (expert layers, held); the
    experts every token picked (expert layers, batch, T, k)."""
    with jax.named_scope("embed"):
        x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, config.dtype)
        positions = rope.document_positions(segment_ids)
    policy = lm_layers.policy(_keeps(config, params, tokens.shape))
    routed = []
    for i in range(config.num_hidden_layers):
        name, dense = f"layer_{i}", _is_dense(config, i)
        mlp_p = params["dense_mlp"][name] if dense else (
            params["router"][name], params["experts"][name], params["shared"][name])
        layer = jax.checkpoint(_layer, static_argnums=(0, 1), policy=policy)
        x, r = layer(config, dense, params["attention"][name], mlp_p, params["norms"][name], x, segment_ids, positions)
        if not dense:
            routed.append(r)
    balance, rows, picks = zip(*routed)
    return x, sum(balance), jnp.stack(rows), jnp.stack(picks)


def logits_of(config: DeepseekV2Config, params: dict, hidden):
    """float32 logits over the rows of the head held here."""
    with jax.named_scope("lm_head"):
        x = lm_layers.rms_norm(hidden, params["norms"]["final"], config.rms_norm_eps)
        return lm_layers.head_logits(_cast(config), x, params["head"]["rows"])


class DeepseekV2:
    """The model as the train state and the loop hold it (as
    models/granite_hybrid.py::GraniteHybrid)."""

    # the STEP_SCOPES (train/step.py, with what lies beneath each) a step of this model enters
    scopes = ("embed", "mla", "dense_mlp", "moe", "lm_head", "loss")

    def __init__(self, config: DeepseekV2Config):
        self.config = config

    def init(self, rng: jax.Array, tokens=None) -> dict:
        del tokens  # the parameters do not depend on the sequence's length
        return {"params": init_params(self.config, rng)}

    def describe(self) -> str:
        c = self.config
        return (f"deepseek v2, {c.num_hidden_layers} layers ({c.first_k_dense_replace} dense), "
                f"{len(c.experts_held)} of {c.experts_total} experts held, {c.num_experts_per_tok} a token")

    def apply(self, variables: dict, tokens, segment_ids, train: bool = False):
        del train  # no dropout, no batch statistics
        params = variables["params"]
        return logits_of(self.config, params, hidden_states(self.config, params, tokens, segment_ids)[0])

    def loss(self, params: dict, tokens, segment_ids):
        """``(loss, the step's scalars)`` as the language-model task
        (train/task.py::LMTask) differentiates and logs them: the next-token
        cross-entropy plus the balance loss, and the routing counters."""
        config = self.config
        hidden, balance, rows, _ = hidden_states(config, params, tokens, segment_ids)
        logits = logits_of(config, params, hidden)
        with jax.named_scope("loss"):
            cross_entropy, counted = lm_layers.next_token_loss(logits, tokens, segment_ids)
            aux = config.aux_loss_alpha * balance
            loss = cross_entropy + aux
        return loss, {"loss": loss, "tokens_counted": counted, "moe/aux_loss": aux, "moe/rows_held": jnp.sum(rows),
                      "moe/rows_max_expert": jnp.max(rows), "moe/rows_min_expert": jnp.min(rows),
                      **attention.step_counters(segment_ids)}

    def picks(self, params: dict, tokens, segment_ids):
        """The experts every token picked, (expert layers, batch, T, k): what
        the benchmark's check compares with its reference's picks."""
        return hidden_states(self.config, params, tokens, segment_ids)[3]

    def run_meta(self, bucket) -> dict[str, Any]:
        """Which lowering the step's attention (ops/attention.py), its grouped
        products and the row movements around them (ops/moe.py) take, what its
        recomputed layers keep, and the share of the experts held."""
        config, backend = self.config, jax.default_backend()
        params = lm_layers.param_shapes(init_params, config)
        return {**attention.run_meta(backend, bucket[1]), **lm_layers.run_meta(_keeps(config, params, bucket)),
                "moe_lowering": _moe_lowering(config, *bucket),
                "moe_rows_lowering": moe.rows_lowering(backend, bucket[0] * bucket[1], config.num_experts_per_tok,
                                                       config.hidden_size, config.moe_intermediate_size),
                "experts_held": len(config.experts_held), "experts_total": config.experts_total}
