"""Nemotron-H: a decoder in which every layer is ONE mixer and nothing else,
a Mamba-2 scan whose heads share B and C in groups (``M``), a feed-forward
part of routed and shared squared-ReLU experts (``E``), or grouped-query
attention (``*``), with an untied head.  This chip may hold a SHARE of each
expert layer's routed experts (``experts_held``): the router stays whole.

Equations (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type`` ``nemotron_h``; ``d`` the hidden size, eps
``layer_norm_epsilon``):

- ``x0 = E[tokens]`` (no multiplier).  Layer ``i`` of kind ``pattern[i]``:
  ``x' = x + Mixer_i(RMSNorm(x; w_i))``.  No residual or logit multipliers.
- ``M`` (scope ``mamba``): ``[z, xBC, dt] = W_in u`` with ``inner =
  mamba_num_heads x mamba_head_dim`` (NOT ``expand x d``), ``W_in`` ``d x
  (inner + (inner + 2 G N) + heads)``, no bias; ``xBC = silu(conv1d(xBC) +
  b)`` (depthwise over ``inner + 2 G N`` channels, causal, not reaching into
  the previous document); ``X`` (T, heads, head size), ``B``, ``C`` (T, G, N);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; head ``h``
  reads group ``h // (heads / G)``: the recurrence of ops/ssd.py (a kernel
  pair on a TPU, XLA elsewhere) plus ``D X``; then ``RMSNorm(y * silu(z)) w``
  OVER EACH GROUP'S ``inner / G`` CHANNELS SEPARATELY (Granite norms all of
  them together); ``W_out`` ``inner x d``, no bias.
- ``E`` (scope ``moe``): ``l = u W_r`` (``d x experts_total``) in float32 at
  ``highest``; ``s = sigmoid(l)``; the picks are the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` =
  ``e_score_correction_bias``: it moves the choice and nothing else);
  weights ``w_e = s_e / sum over the picked of s`` (without ``b``) times
  ``routed_scaling_factor``.  ``E_e(u) = W_down,e relu(W_up,e u)^2``, no
  gate, no bias; ``F(u) = sum over the picked experts HELD here of w_e E_e(u)
  + Sh(u)``, ``Sh`` one expert of the same form at the shared width.  The
  weights are normalised over all the picks, held or not; what an absent
  expert would add is left out.  No balance loss: the config has none.
- ``*`` (scope ``attention``): ``softmax(q k^T / sqrt(head_dim))`` causal
  and within one document (ops/attention.py), ``num_key_value_heads`` shared
  by the query heads in order, no bias, then ``W_o``.
- ``logits = RMSNorm(x_L) H^T`` in float32 (``H`` the untied head's rows held
  here); the loss is ``lm_layers.next_token_loss``.

Departures and readings, each listed under ``assumed`` in the benchmark's
configuration file:

- [rotary] the attention layers carry NO positional encoding (the family's
  attention layers have none; ``rope_theta`` is a key the layer does not
  read).  The other reading is ``attention_rotary=True``: plain rotary by the
  position inside the document on all ``head_dim`` channels.  One switch,
  here and in the reference, until it is checked against the hub.
- [bias] ``e_score_correction_bias`` is a buffer that no gradient trains and
  whose update rule is not in the configuration: it is a CONSTANT of the
  program (``router_bias``, zeros unless given), not a parameter.
- [dt] no clamp of ``dt`` (``time_step_*`` are initialisation keys);
  ``rescale_prenorm_residual`` is an initialisation key and is not applied.
- [chunk] the published ``chunk_size`` is a schedule of the published kernels,
  not a shape of the model: the program scans in its own ``mamba_chunk_size``.
- [1e-20] the published code adds 1e-20 to the sum of the picked scores.

Plain functions over a parameter tree whose top level is the kind of
parameter (``embed``, ``mamba``, ``attention``, ``router``, ``experts``,
``shared``, ``norms``, ``head``).  float32 parameters; ``config.dtype``
(bfloat16) activations and matmul operands; float32 norms, router, softmax,
the scan's decays and state, and loss.  Every layer is recomputed in the
backward pass from its input; where the attention kernels run their output and
log-sum-exp are kept too (``lm_layers.layer_keeps``; no layer here calls
``gated_mlp``, so nothing carries ``lm_layers.MLP_GATE_UP``).  Single device.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.ops import attention, document_conv, moe, rope, ssd

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
SCOPE = {MAMBA: "mamba", EXPERTS: "moe", ATTENTION: "attention"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int
    hidden_size: int
    pattern: str  # a letter a layer: M, E or *
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    experts_total: int  # the router's width: the model's routed experts
    experts_held: tuple[int, ...]  # the ids of those this chip computes
    num_experts_per_tok: int
    routed_scaling_factor: float = 1.0
    conv_kernel: int = 4
    layer_norm_epsilon: float = 1e-5
    rope_theta: float = 10000.0
    attention_rotary: bool = False  # [rotary]
    router_bias: tuple[tuple[float, ...], ...] = ()  # [bias] a row an expert layer; () is zeros
    mamba_chunk_size: int = 256  # [chunk]
    dtype: Any = jnp.bfloat16
    attention_q_block: int = 1024  # as GraniteHybridConfig's

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "NemotronHConfig":
        """From the keys of the published ``config.json``; refuses what this
        model does not compute rather than ignoring it.  ``n_routed_experts``
        counts the experts HELD; a cut configuration adds
        ``n_routed_experts_total`` (the router's width) and ``experts_held``
        (their ids), without which all are held."""
        want = {"n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
                "tie_word_embeddings": False, "norm_topk_prob": True, "n_shared_experts": 1,
                "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
                "use_conv_bias": True, "residual_in_fp32": False}
        wrong = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
        if wrong:
            raise ValueError(f"nemotron_h does not compute {wrong}; it computes {want}")
        pattern = hf["hybrid_override_pattern"]
        if len(pattern) != hf["num_hidden_layers"] or set(pattern) - set(SCOPE):
            raise ValueError(f"hybrid_override_pattern {pattern!r}: {hf['num_hidden_layers']} layers of {sorted(SCOPE)}")
        held = tuple(hf.get("experts_held", range(hf["n_routed_experts"])))
        total = hf.get("n_routed_experts_total", hf["n_routed_experts"])
        if len(held) != hf["n_routed_experts"] or len(set(held)) != len(held) or not all(0 <= e < total for e in held):
            raise ValueError(f"experts_held {held} for n_routed_experts {hf['n_routed_experts']} of {total}")
        if hf["mamba_num_heads"] % hf["n_groups"]:
            raise ValueError(f"{hf['mamba_num_heads']} mamba heads in {hf['n_groups']} groups")
        keys = {f.name for f in dataclasses.fields(cls)} - {"pattern", "experts_total", "experts_held", "dtype"}
        given = {k: hf[k] for k in keys if k in hf}
        given["router_bias"] = tuple(tuple(row) for row in given.get("router_bias", ()))
        return cls(pattern=pattern, experts_total=total, experts_held=held, **{**given, **overrides})


# The CPU tests' and ``train.py lm-synthetic --model tiny-nemotron``'s: all
# three kinds of layer at toy widths, 2 groups, 2 of 8 experts held, 3 a
# token, an expert width that is not whole lane tiles.
TINY = NemotronHConfig(
    vocab_size=128, hidden_size=64, pattern="MEM*E", num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, experts_total=8, experts_held=(0, 1), num_experts_per_tok=3,
    routed_scaling_factor=2.5, mamba_chunk_size=8, attention_q_block=32,
)

# What is set here and not by the published configuration (the benchmark's
# configuration file lists them under ``assumed``): as models/granite_hybrid.py's.
INIT_STD = 0.02
A_INIT_RANGE = (1.0, 16.0)
DT_INIT_RANGE = (1e-3, 1e-1)


def init_params(config: NemotronHConfig, rng: jax.Array) -> dict:
    d, inner, heads = config.hidden_size, config.mamba_d_inner, config.mamba_num_heads
    conv_dim = inner + 2 * config.n_groups * config.ssm_state_size
    q, kv = config.num_attention_heads * config.head_dim, config.num_key_value_heads * config.head_dim
    width, held = config.moe_intermediate_size, len(config.experts_held)
    shared = config.moe_shared_expert_intermediate_size

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = iter(jax.random.split(rng, 2 + 5 * len(config.pattern)))
    params: dict = {"embed": {"embedding": normal(next(keys), (config.vocab_size, d))},
                    "mamba": {}, "attention": {}, "router": {}, "experts": {}, "shared": {},
                    "norms": {"final": ones(d)}, "head": {"rows": normal(next(keys), (config.vocab_size, d))}}
    for i, kind in enumerate(config.pattern):
        name = f"layer_{i}"
        params["norms"][name] = ones(d)
        if kind == MAMBA:
            dt = jnp.exp(jax.random.uniform(next(keys), (heads,), jnp.float32,
                                            math.log(DT_INIT_RANGE[0]), math.log(DT_INIT_RANGE[1])))
            params["mamba"][name] = {
                "in_proj": normal(next(keys), (d, inner + conv_dim + heads)),
                "conv_w": normal(next(keys), (config.conv_kernel, conv_dim)),
                "conv_b": jnp.zeros((conv_dim,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(jax.random.uniform(next(keys), (heads,), jnp.float32, *A_INIT_RANGE)),
                "D": ones(heads),
                "norm_w": ones(inner),
                "out_proj": normal(next(keys), (inner, d)),
            }
        elif kind == ATTENTION:
            params["attention"][name] = {"q": normal(next(keys), (d, q)), "k": normal(next(keys), (d, kv)),
                                         "v": normal(next(keys), (d, kv)), "o": normal(next(keys), (q, d))}
        else:
            params["router"][name] = {"gate": normal(next(keys), (d, config.experts_total))}
            params["experts"][name] = {"up": normal(next(keys), (held, d, width)),
                                       "down": normal(next(keys), (held, width, d))}
            params["shared"][name] = {"up": normal(next(keys), (d, shared)), "down": normal(next(keys), (shared, d))}
    return params


def _operand(config, x):
    """An operand of a matmul with a weight, in ``config.dtype``."""
    return x.astype(config.dtype)


def _cast(config):
    # bound late: the benchmark's control replaces this module's ``_operand``
    return lambda x: _operand(config, x)


def _matmul(config, x, w):
    return lm_layers.matmul(_cast(config), x, w)


def _group_norm(config, y, w):
    """RMSNorm over each group's ``inner / G`` channels separately, times ``w``."""
    by_group = y.reshape(*y.shape[:-1], config.n_groups, -1)
    w = w.reshape(config.n_groups, -1)
    return lm_layers.rms_norm(by_group, w, config.layer_norm_epsilon).reshape(y.shape)


def _mamba(config, p, u, segment_ids):
    inner, n, heads, groups = config.mamba_d_inner, config.ssm_state_size, config.mamba_num_heads, config.n_groups
    batch, t, _ = u.shape
    with jax.named_scope("in_proj"):
        z, xbc, dt = jnp.split(_matmul(config, u, p["in_proj"]), [inner, 2 * inner + 2 * groups * n], axis=-1)
    with jax.named_scope("conv"):
        xbc = lm_layers.document_conv_silu(xbc, p["conv_w"], p["conv_b"], segment_ids).astype(config.dtype)
        x, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
        x = x.reshape(batch, t, heads, config.mamba_head_dim)
        b, c = b.reshape(batch, t, groups, n), c.reshape(batch, t, groups, n)
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [dt]
        y = ssd.ssd_chunked(x, dt, -jnp.exp(p["A_log"]), b, c, segment_ids, config.mamba_chunk_size)
        y = y + p["D"][:, None] * x.astype(jnp.float32)
    with jax.named_scope("gate_norm"):
        y = y.reshape(batch, t, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = _group_norm(config, y, p["norm_w"]).astype(config.dtype)
    with jax.named_scope("out_proj"):
        return _matmul(config, y, p["out_proj"])


def _attention(config, p, u, segment_ids):
    batch, t, _ = u.shape
    hd = config.head_dim
    q = _matmul(config, u, p["q"]).reshape(batch, t, config.num_attention_heads, hd)
    k = _matmul(config, u, p["k"]).reshape(batch, t, config.num_key_value_heads, hd)
    v = _matmul(config, u, p["v"]).reshape(batch, t, config.num_key_value_heads, hd)
    if config.attention_rotary:  # [rotary]
        positions = rope.document_positions(segment_ids)
        inv_freq = 1.0 / config.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        q, k = rope.apply_rotary(q, positions, inv_freq), rope.apply_rotary(k, positions, inv_freq)
    out = attention.packed_causal_attention(q, k, v, segment_ids, hd ** -0.5, config.attention_q_block)
    return _matmul(config, out.reshape(batch, t, -1), p["o"])


def _moe_lowering(config, batch: int, t: int) -> str:
    return moe.lowering(jax.default_backend(), batch * t * config.num_experts_per_tok, config.hidden_size,
                        config.moe_intermediate_size)


def _router_bias(config, index: int):
    """[bias] of the ``index``-th expert layer."""
    if not config.router_bias:
        return jnp.zeros((config.experts_total,), jnp.float32)
    return jnp.asarray(config.router_bias[index], jnp.float32)


def _moe(config, index, router, experts, shared, u):
    """-> (F(u) in ``u``'s dtype, (the rows routed here by held expert, the
    picks (batch, T, k)))."""
    batch, t, _ = u.shape
    k = config.num_experts_per_tok
    route = functools.partial(moe.route_sigmoid, bias=_router_bias(config, index), scale=config.routed_scaling_factor)
    routed, routing, plan = moe.expert_layer(
        u, router["gate"], _operand(config, experts["up"]), _operand(config, experts["down"]),
        config.experts_held, k, _moe_lowering(config, batch, t), router=route, mlp=moe.experts_relu2)
    with jax.named_scope("shared"):
        out = routed + lm_layers.relu2_mlp(_cast(config), shared, u).astype(jnp.float32)
    return out.astype(u.dtype), (plan.group_sizes, routing.picks.reshape(batch, t, k))


def _layer(config, kind, index, p, norm, x, segment_ids):
    """``x + Mixer(RMSNorm(x))``; ``index`` counts the expert layers before
    this one; an expert layer also returns its routing."""
    with jax.named_scope(SCOPE[kind]):
        u = lm_layers.rms_norm(x, norm, config.layer_norm_epsilon)
        if kind == MAMBA:
            return x + _mamba(config, p, u, segment_ids).astype(x.dtype), None
        if kind == ATTENTION:
            return x + _attention(config, p, u, segment_ids).astype(x.dtype), None
        f, routed = _moe(config, index, *p, u)
        return x + f, routed


def hidden_states(config: NemotronHConfig, params: dict, tokens, segment_ids):
    """``(x, rows, picks)``: the last layer's output before the final norm
    (batch, T, d); the rows routed here (expert layers, held); the experts
    every token picked (expert layers, batch, T, k)."""
    with jax.named_scope("embed"):
        x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, config.dtype)
    policy = lm_layers.policy(lm_layers.NO_PRODUCT)
    routed = []
    for i, kind in enumerate(config.pattern):
        name = f"layer_{i}"
        p = (params["router"][name], params["experts"][name], params["shared"][name]) if kind == EXPERTS else (
            params[SCOPE[kind]][name])
        layer = jax.checkpoint(_layer, static_argnums=(0, 1, 2), policy=policy)
        x, r = layer(config, kind, len(routed), p, params["norms"][name], x, segment_ids)
        if kind == EXPERTS:
            routed.append(r)
    rows, picks = zip(*routed)
    return x, jnp.stack(rows), jnp.stack(picks)


def logits_of(config: NemotronHConfig, params: dict, hidden):
    """float32 logits over the rows of the head held here."""
    with jax.named_scope("lm_head"):
        x = lm_layers.rms_norm(hidden, params["norms"]["final"], config.layer_norm_epsilon)
        return lm_layers.head_logits(_cast(config), x, params["head"]["rows"])


class NemotronH:
    """The model as the train state and the loop hold it (as
    models/granite_hybrid.py::GraniteHybrid)."""

    # the STEP_SCOPES (train/step.py, with what lies beneath each) a step of this model enters
    scopes = ("embed", "mamba", "attention", "moe", "lm_head", "loss")

    def __init__(self, config: NemotronHConfig):
        self.config = config

    def init(self, rng: jax.Array, tokens=None) -> dict:
        del tokens  # the parameters do not depend on the sequence's length
        return {"params": init_params(self.config, rng)}

    def describe(self) -> str:
        c = self.config
        kinds = ", ".join(f"{c.pattern.count(k)} {SCOPE[k]}" for k in SCOPE)
        return (f"nemotron-h, {len(c.pattern)} layers ({kinds}), {len(c.experts_held)} of {c.experts_total} "
                f"experts held, {c.num_experts_per_tok} a token")

    def apply(self, variables: dict, tokens, segment_ids, train: bool = False):
        del train  # no dropout, no batch statistics
        params = variables["params"]
        return logits_of(self.config, params, hidden_states(self.config, params, tokens, segment_ids)[0])

    def loss(self, params: dict, tokens, segment_ids):
        """``(loss, the step's scalars)`` as the language-model task
        (train/task.py::LMTask) differentiates and logs them: the next-token
        cross-entropy, and the routing counters over the expert layers."""
        hidden, rows, _ = hidden_states(self.config, params, tokens, segment_ids)
        logits = logits_of(self.config, params, hidden)
        with jax.named_scope("loss"):
            loss, counted = lm_layers.next_token_loss(logits, tokens, segment_ids)
        return loss, {"loss": loss, "tokens_counted": counted, "moe/rows_held": jnp.sum(rows),
                      "moe/rows_max_expert": jnp.max(rows), "moe/rows_min_expert": jnp.min(rows),
                      **attention.step_counters(segment_ids)}

    def picks(self, params: dict, tokens, segment_ids):
        """The experts every token picked, (expert layers, batch, T, k): what
        the benchmark's check compares with its reference's picks."""
        return hidden_states(self.config, params, tokens, segment_ids)[2]

    def run_meta(self, bucket) -> dict[str, Any]:
        """Which lowering the step's attention (ops/attention.py), its scans
        (ops/ssd.py), the convolutions before them (ops/document_conv.py), its
        grouped products and the row movements around them (ops/moe.py) take, the
        scan's groups, what its recomputed layers keep and the share of the experts held."""
        config, backend = self.config, jax.default_backend()
        return {**attention.run_meta(backend, bucket[1]), **lm_layers.run_meta(lm_layers.NO_PRODUCT),
                "ssd_lowering": ssd.lowering(backend, bucket[1], config.mamba_chunk_size, config.mamba_num_heads,
                                             config.mamba_head_dim, config.ssm_state_size, config.n_groups),
                "ssd_groups": config.n_groups,
                "conv_lowering": document_conv.lowering(
                    backend, bucket[1], config.mamba_d_inner + 2 * config.n_groups * config.ssm_state_size,
                    config.conv_kernel),
                "moe_lowering": _moe_lowering(config, *bucket),
                "moe_rows_lowering": moe.rows_lowering(backend, bucket[0] * bucket[1], config.num_experts_per_tok,
                                                       config.hidden_size, config.moe_intermediate_size),
                "experts_held": len(config.experts_held), "experts_total": config.experts_total}
