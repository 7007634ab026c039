"""Arcee's Trinity family (``model_type`` ``afmoe``): a decoder of grouped-query
attention layers of TWO kinds, sliding-window and full, with a gate on
attention's output and a norm on every sublayer's output beside the one on its
input, over leading dense MLPs and then sigmoid-routed experts with a shared
one, with an untied head.  This chip may hold a SHARE of each layer's routed
experts (``experts_held``): the router stays whole.

Equations (arcee-ai/Trinity-Mini ``config.json``; what the config has no key
for follows the family's public modelling code and is marked [assumed], as the
benchmark's configuration file lists it; ``d`` the hidden size, ``p_t`` a
token's position inside its own document):

- ``x0 = sqrt(d) E[tokens]`` (``mup_enabled``); layer ``i``:
  ``x' = x + RMSNorm(Attn_i(RMSNorm(x)))``, ``x'' = x' + RMSNorm(F_i(RMSNorm(x')))``:
  four norms a layer [assumed].  The two on the OUTPUTS start their scales at
  ``output_norm_init`` (1 unless the configuration says otherwise: a depth-scaled
  start of ``o`` and ``down`` would be divided out by them), all others at 1.
- ``Attn_i`` (scope ``attention``), ``u = RMSNorm(x)``: ``q = W_q u`` (heads of
  ``head_dim``), ``k = W_k u``, ``v = W_v u`` (``num_key_value_heads``),
  ``g = W_g u`` (as wide as ``q``), no bias; ``q`` and ``k`` RMSNorm over each
  head's channels [assumed].  ``layer_types[i] == "sliding_attention"``: rotary
  on ``q`` and ``k`` (``rope_theta``, all channels, channel ``j`` with ``j +
  head_dim / 2``, by ``p_t``), and query ``t`` sees key ``s`` iff same document,
  ``s <= t`` and ``t - s < sliding_window`` (scope ``window_core``).
  ``"full_attention"``: NO rotation [assumed], ``s <= t`` in the same document
  (scope ``full_core``).  Softmax of ``q . k / sqrt(head_dim)``;
  ``W_o (heads * sigmoid(g))`` [assumed: the gate].
- ``F_i``, ``i < num_dense_layers`` (scope ``dense_mlp``): a SiLU-gated MLP of
  ``intermediate_size``.
- ``F_i`` otherwise (scope ``moe``): ``s = sigmoid_float32(u W_r)`` over ALL
  ``experts_total``; the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
  ``expert_bias`` buffer, a constant: it moves the choice and nothing else);
  ``w_e = route_scale s_e / sum over the picks of s`` (``route_norm``); the sum
  over the picked experts HELD here of ``w_e E_e(u)`` plus ``Sh(u)``, all
  SiLU-gated MLPs of ``moe_intermediate_size`` (``Sh``: x ``num_shared_experts``).
  No balance loss (``load_balance_coeff`` is the rate of the bias's update in
  the family's training code, not a loss; the update is not run here).
- ``logits = RMSNorm(x_L) H^T`` (``H`` the untied head's rows held here).

Plain functions over a parameter tree as models/deepseek_v2.py: the top level
is the kind of parameter (``embed``, ``attention``, ``dense_mlp``, ``router``,
``experts``, ``shared``, ``norms``, ``head``).  float32 parameters;
``config.dtype`` (bfloat16) activations and matmul operands; float32 norms (the
four of a layer and the two of its heads), router, rotary angles, the sigmoid
gate's argument, softmax accumulators, logits and loss.  Every layer is
recomputed in the backward pass; of its inside the attention kernels' output
and log-sum-exp are kept and, where the device has the room, the dense and
shared MLPs' products with ``gate_up`` (``lm_layers.layer_keeps``).  Single
device.

Between its projections and the attention kernels a layer crosses in one of two
ways, chosen from what the step sees (``_edges``: ``attention_edges.lowering``;
``run_meta`` says ``attention_edges``).  Where the kernels run and a head is whole
lane tiles (a TPU, T of whole blocks, ``head_dim % 128 == 0``: the published model
at its cell's bucket) by ops/attention_edges.py's two passes
(``_attention_by_passes``): per-head norm, rotation and softmax scale in float32
inside ONE pass over ``config.dtype`` that also writes the kernels' head-major
layout, rounded ONCE, and the way back with the gate likewise.  Everywhere else
(the CPU, the tiny preset's heads of 16, a ragged T) by the lines of
``_attention`` as written, which round q three times on the way (after the norm,
after the rotation, after the scale: 128^-0.5 is no power of two), go through
float32 copies in HBM, and are what the benchmark's mutations patch BY NAME
(``lm_layers.rms_norm`` on four dimensions, ``rope.apply_rotary_halves``,
``attention.packed_causal_attention``, ``jax.nn.sigmoid``): keep them calling
those.  The passes are closer to the float32 reference, never further; what is
float32 in the lines (the norms' reductions, angles, cosines, sines, the gate's
argument, the norm scales' gradients) is float32 in the passes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.ops import attention, attention_edges, moe, rope

SLIDING, FULL = "sliding_attention", "full_attention"
CORE_SCOPE = {SLIDING: "window_core", FULL: "full_core"}


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int  # a leading dense layer's MLP
    moe_intermediate_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: tuple[str, ...]  # a layer's attention: SLIDING or FULL
    sliding_window: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    experts_total: int  # the router's width: the model's routed experts
    experts_held: tuple[int, ...]  # the ids of those this chip computes
    num_experts_per_tok: int
    num_shared_experts: int = 1
    route_scale: float = 1.0
    expert_bias: tuple[tuple[float, ...], ...] = ()  # a row an expert layer; () is zeros
    output_norm_init: float = 1.0  # what the scales of a layer's two OUTPUT norms start at (``init_params``)
    mup_enabled: bool = True  # the embedding times sqrt(d)
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_q_block: int = 1024  # as GraniteHybridConfig's; the xla lowering's blocks of queries

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "AfmoeConfig":
        """From the keys of the published ``config.json``; refuses what this
        model does not compute rather than ignoring it.  ``num_experts`` counts
        the experts HELD; a cut configuration adds ``num_experts_total`` (the
        router's width) and ``experts_held`` (their ids), without which all are
        held."""
        want = {"n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1, "score_func": "sigmoid",
                "route_norm": True, "hidden_act": "silu", "rope_scaling": None, "tie_word_embeddings": False}
        wrong = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
        if wrong:
            raise ValueError(f"afmoe does not compute {wrong}; it computes {want}")
        kinds = tuple(hf["layer_types"])
        if len(kinds) != hf["num_hidden_layers"] or set(kinds) - set(CORE_SCOPE):
            raise ValueError(f"layer_types {kinds}: {hf['num_hidden_layers']} layers of {sorted(CORE_SCOPE)}")
        held = tuple(hf.get("experts_held", range(hf["num_experts"])))
        total = hf.get("num_experts_total", hf["num_experts"])
        if len(held) != hf["num_experts"] or len(set(held)) != len(held) or not all(0 <= e < total for e in held):
            raise ValueError(f"experts_held {held} for num_experts {hf['num_experts']} of {total}")
        keys = {f.name for f in dataclasses.fields(cls)} - {"layer_types", "experts_total", "experts_held", "dtype"}
        given = {k: hf[k] for k in keys if k in hf}
        given["expert_bias"] = tuple(tuple(row) for row in given.get("expert_bias", ()))
        return cls(layer_types=kinds, experts_total=total, experts_held=held, **{**given, **overrides})


# The CPU tests' and ``train.py lm-synthetic --model tiny-afmoe``'s: one dense layer and three expert layers at toy
# widths, both kinds of attention, a window of 16 keys (shorter than one document of the tests' packings, longer
# than another), 2 of 8 experts held, 3 a token, an expert width that is not whole lane tiles.
TINY = AfmoeConfig(
    vocab_size=128, hidden_size=64, intermediate_size=96, moe_intermediate_size=24, num_hidden_layers=4,
    num_dense_layers=1, layer_types=(SLIDING, SLIDING, FULL, SLIDING), sliding_window=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, experts_total=8, experts_held=(0, 1), num_experts_per_tok=3,
    route_scale=2.5, attention_q_block=32,
)

INIT_STD = 0.02  # ``initializer_range`` is not in the catalog's copy: ``assumed`` in the benchmark's file


def _is_dense(config: AfmoeConfig, i: int) -> bool:
    return i < config.num_dense_layers


def init_params(config: AfmoeConfig, rng: jax.Array) -> dict:
    d, hd = config.hidden_size, config.head_dim
    q, kv = config.num_attention_heads * hd, config.num_key_value_heads * hd
    width, held = config.moe_intermediate_size, len(config.experts_held)

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = iter(jax.random.split(rng, 2 + 10 * config.num_hidden_layers))
    params: dict = {"embed": {"embedding": normal(next(keys), (config.vocab_size, d))},
                    "attention": {}, "dense_mlp": {}, "router": {}, "experts": {}, "shared": {},
                    "norms": {"final": ones(d)}, "head": {"rows": normal(next(keys), (config.vocab_size, d))}}
    for i in range(config.num_hidden_layers):
        name = f"layer_{i}"
        params["attention"][name] = {
            "q": normal(next(keys), (d, q)), "k": normal(next(keys), (d, kv)), "v": normal(next(keys), (d, kv)),
            "gate": normal(next(keys), (d, q)), "o": normal(next(keys), (q, d)), "q_norm": ones(hd), "k_norm": ones(hd)}
        out = config.output_norm_init * ones(d)
        params["norms"][name] = {"attention_in": ones(d), "attention_out": out, "mlp_in": ones(d), "mlp_out": out}
        if _is_dense(config, i):
            ff = config.intermediate_size
            params["dense_mlp"][name] = {"gate_up": normal(next(keys), (d, 2 * ff)), "down": normal(next(keys), (ff, d))}
            continue
        shared = config.num_shared_experts * width
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


def _edges(config, t: int) -> str:
    """How a layer crosses between its projections and its attention kernels over ``t`` tokens a sequence:
    ``kernel`` (ops/attention_edges.py's two passes) or ``xla`` (the lines of ``_attention``)."""
    return attention_edges.lowering(jax.default_backend(), t, config.head_dim)


def _attention(config, kind: str, p, u, segment_ids, positions):
    batch, t, _ = u.shape
    hd, eps = config.head_dim, config.rms_norm_eps
    if _edges(config, t) == attention_edges.KERNEL:
        return _attention_by_passes(config, kind, p, u, segment_ids, positions)
    q = _matmul(config, u, p["q"]).reshape(batch, t, config.num_attention_heads, hd)
    k = _matmul(config, u, p["k"]).reshape(batch, t, config.num_key_value_heads, hd)
    v = _matmul(config, u, p["v"]).reshape(batch, t, config.num_key_value_heads, hd)
    q, k = lm_layers.rms_norm(q, p["q_norm"], eps), lm_layers.rms_norm(k, p["k_norm"], eps)
    if kind == SLIDING:  # the full layers see no position at all
        angles = positions.astype(jnp.float32)[..., None] * rope.plain_inv_freq(hd, config.rope_theta)
        q, k = rope.apply_rotary_halves(q, angles), rope.apply_rotary_halves(k, angles)
    with jax.named_scope(CORE_SCOPE[kind]):
        out = attention.packed_causal_attention(q, k, v, segment_ids, hd ** -0.5, config.attention_q_block,
                                                window=config.sliding_window if kind == SLIDING else None)
    gate = jax.nn.sigmoid(_matmul(config, u, p["gate"]).astype(jnp.float32))
    return _matmul(config, out.reshape(batch, t, -1).astype(jnp.float32) * gate, p["o"])


def _attention_by_passes(config, kind: str, p, u, segment_ids, positions):
    """``_attention`` where the attention kernels run and a head is whole lane tiles: the same equations
    with each crossing ONE pass (ops/attention_edges.py: q and k normalised, rotated and scaled in float32,
    rounded ONCE where the lines above round three times; the way back with the gate as above)."""
    hd, eps = config.head_dim, config.rms_norm_eps
    angles = window = None  # the full layers see no position at all
    if kind == SLIDING:
        angles = positions.astype(jnp.float32)[..., None] * rope.plain_inv_freq(hd, config.rope_theta)
        window = config.sliding_window
    q = attention_edges.heads_in(_matmul(config, u, p["q"]), p["q_norm"], angles, eps, hd ** -0.5)
    k = attention_edges.heads_in(_matmul(config, u, p["k"]), p["k_norm"], angles, eps, 1.0)
    v = attention_edges.head_major(_matmul(config, u, p["v"]), config.num_key_value_heads)
    with jax.named_scope(CORE_SCOPE[kind]):
        out = attention.head_major_attention(segment_ids, config.num_attention_heads, window=window)(q, k, v)
    return _matmul(config, attention_edges.heads_out(out, _matmul(config, u, p["gate"])), p["o"])


def _moe_lowering(config, batch: int, t: int) -> str:
    return moe.lowering(jax.default_backend(), batch * t * config.num_experts_per_tok, config.hidden_size,
                        config.moe_intermediate_size)


def _expert_bias(config, index: int):
    """``expert_bias`` of the ``index``-th expert layer."""
    if not config.expert_bias:
        return jnp.zeros((config.experts_total,), jnp.float32)
    return jnp.asarray(config.expert_bias[index], jnp.float32)


def _moe(config, index, router, experts, shared, u):
    """-> (F(u) float32, (the rows routed here by held expert, the picks
    (batch, T, k)))."""
    batch, t, _ = u.shape
    k = config.num_experts_per_tok
    route = functools.partial(moe.route_sigmoid, bias=_expert_bias(config, index), scale=config.route_scale)
    routed, routing, plan = moe.expert_layer(
        u, router["gate"], _operand(config, experts["gate_up"]), _operand(config, experts["down"]),
        config.experts_held, k, _moe_lowering(config, batch, t), router=route)
    with jax.named_scope("shared"):
        out = routed + lm_layers.gated_mlp(_cast(config), shared, u).astype(jnp.float32)
    return out, (plan.group_sizes, routing.picks.reshape(batch, t, k))


def _layer(config, kind: str, index: int | None, attn_p, mlp_p, norms, x, segment_ids, positions):
    """``index``: ``None`` for a dense layer, else how many expert layers lie
    before this one; an expert layer also returns its routing."""
    eps = config.rms_norm_eps
    with jax.named_scope("attention"):
        u = lm_layers.rms_norm(x, norms["attention_in"], eps)
        a = _attention(config, kind, attn_p, u, segment_ids, positions)
        h = x + lm_layers.rms_norm(a, norms["attention_out"], eps).astype(x.dtype)
    if index is None:
        with jax.named_scope("dense_mlp"):
            u = lm_layers.rms_norm(h, norms["mlp_in"], eps)
            f = lm_layers.gated_mlp(_cast(config), mlp_p, u)
            return h + lm_layers.rms_norm(f, norms["mlp_out"], eps).astype(x.dtype), None
    with jax.named_scope("moe"):
        u = lm_layers.rms_norm(h, norms["mlp_in"], eps)
        f, routed = _moe(config, index, *mlp_p, u)
        return h + lm_layers.rms_norm(f, norms["mlp_out"], eps).astype(x.dtype), routed


def _keeps(config, params, bucket) -> lm_layers.Keeps:
    """What the recomputed layers of a step over ``bucket`` (sequences, tokens) keep: a dense layer's
    gated MLP, an expert layer's shared one (``lm_layers.layer_keeps``)."""
    widths = [config.intermediate_size if _is_dense(config, i) else config.num_shared_experts * config.moe_intermediate_size
              for i in range(config.num_hidden_layers)]
    return lm_layers.keeps_of(widths, params, bucket, config.hidden_size, config.dtype)


def hidden_states(config: AfmoeConfig, params: dict, tokens, segment_ids):
    """``(x, rows, picks)``: the last layer's output before the final norm
    (batch, T, d); the rows routed here (expert layers, held); the experts
    every token picked (expert layers, batch, T, k)."""
    with jax.named_scope("embed"):
        x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, config.dtype,
                                   math.sqrt(config.hidden_size) if config.mup_enabled else None)
        positions = rope.document_positions(segment_ids)
    policy = lm_layers.policy(_keeps(config, params, tokens.shape))
    routed = []
    for i, kind in enumerate(config.layer_types):
        name, dense = f"layer_{i}", _is_dense(config, i)
        mlp_p = params["dense_mlp"][name] if dense else (
            params["router"][name], params["experts"][name], params["shared"][name])
        layer = jax.checkpoint(_layer, static_argnums=(0, 1, 2), policy=policy)
        x, r = layer(config, kind, None if dense else len(routed), params["attention"][name], mlp_p,
                     params["norms"][name], x, segment_ids, positions)
        if not dense:
            routed.append(r)
    rows, picks = zip(*routed)
    return x, jnp.stack(rows), jnp.stack(picks)


def logits_of(config: AfmoeConfig, params: dict, hidden):
    """float32 logits over the rows of the head held here."""
    with jax.named_scope("lm_head"):
        x = lm_layers.rms_norm(hidden, params["norms"]["final"], config.rms_norm_eps)
        return lm_layers.head_logits(_cast(config), x, params["head"]["rows"])


def _window(config) -> int | None:
    """The window of the layers that have one; nothing where no layer has."""
    return config.sliding_window if SLIDING in config.layer_types else None


class Afmoe:
    """The model as the train state and the loop hold it (as
    models/deepseek_v2.py::DeepseekV2)."""

    # the STEP_SCOPES (train/step.py, with what lies beneath each) a step of this model enters
    scopes = ("embed", "attention", "dense_mlp", "moe", "lm_head", "loss")

    def __init__(self, config: AfmoeConfig):
        self.config = config

    def init(self, rng: jax.Array, tokens=None) -> dict:
        del tokens  # the parameters do not depend on the sequence's length
        return {"params": init_params(self.config, rng)}

    def describe(self) -> str:
        c = self.config
        return (f"afmoe, {c.num_hidden_layers} layers ({c.num_dense_layers} dense; {c.layer_types.count(SLIDING)} with "
                f"a window of {c.sliding_window} keys, {c.layer_types.count(FULL)} full), {len(c.experts_held)} of "
                f"{c.experts_total} experts held, {c.num_experts_per_tok} a token")

    def apply(self, variables: dict, tokens, segment_ids, train: bool = False):
        del train  # no dropout, no batch statistics
        params = variables["params"]
        return logits_of(self.config, params, hidden_states(self.config, params, tokens, segment_ids)[0])

    def loss(self, params: dict, tokens, segment_ids):
        """``(loss, the step's scalars)`` as the language-model task
        (train/task.py::LMTask) differentiates and logs them: the next-token
        cross-entropy, the routing counters over the expert layers, and the
        share of the block pairs the two kinds of attention layer ran."""
        hidden, rows, _ = hidden_states(self.config, params, tokens, segment_ids)
        logits = logits_of(self.config, params, hidden)
        with jax.named_scope("loss"):
            loss, counted = lm_layers.next_token_loss(logits, tokens, segment_ids)
        return loss, {"loss": loss, "tokens_counted": counted, "moe/rows_held": jnp.sum(rows),
                      "moe/rows_max_expert": jnp.max(rows), "moe/rows_min_expert": jnp.min(rows),
                      **attention.step_counters(segment_ids, _window(self.config), self.config.num_attention_heads)}

    def picks(self, params: dict, tokens, segment_ids):
        """The experts every token picked, (expert layers, batch, T, k): what
        the benchmark's check compares with its reference's picks."""
        return hidden_states(self.config, params, tokens, segment_ids)[2]

    def run_meta(self, bucket) -> dict[str, Any]:
        """Which lowering the step's attention (ops/attention.py, with the
        sliding layers' window), its grouped products and the row movements
        around them (ops/moe.py) take, what its recomputed layers keep, and the
        share of the experts held."""
        config, backend = self.config, jax.default_backend()
        params = lm_layers.param_shapes(init_params, config)
        return {**attention.run_meta(backend, bucket[1], _window(config)), "attention_edges": _edges(config, bucket[1]),
                **lm_layers.run_meta(_keeps(config, params, bucket)),
                "moe_lowering": _moe_lowering(config, *bucket),
                "moe_rows_lowering": moe.rows_lowering(backend, bucket[0] * bucket[1], config.num_experts_per_tok,
                                                       config.hidden_size, config.moe_intermediate_size),
                "experts_held": len(config.experts_held), "experts_total": config.experts_total}
