"""Keye-VL-2.0's language model: a decoder whose every layer is grouped-query
attention over the keys a learned INDEXER selects, then routed experts without
a shared one, with an untied head.  The vision tower is not built: tokens are
text.  This chip may hold a SHARE of each layer's routed experts
(``experts_held``): the router stays whole.

Equations (Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``, ``model_type``
``KeyeVL2``: a Qwen3-MoE body with ``sa_config``, DeepSeek Sparse Attention's
indexer; ``d`` the hidden size, ``p_t`` a token's position inside its own
document, ``R`` the rotary map of ``rope_theta`` whose frequency pairs are cut
into ``mrope_section`` runs turning by the temporal, height and width id - for
text all three are ``p_t``):

- ``x0 = E[tokens]``; every layer ``y = x + Attn(RMSNorm(x))``,
  ``x' = y + F(RMSNorm(y))``.
- ``Attn`` (scope ``attention``), ``h = RMSNorm(x)``: ``q = R(RMSNorm_head(W_q h))``
  (heads of ``head_dim``, the norm over one head), ``k = R(RMSNorm_head(W_k h))``,
  ``v = W_v h`` (``num_key_value_heads``), no bias.
  - scope ``indexer``, on ``stop_gradient(h)``: ``qI = R_I(W_qI h)`` (``indexer_num_heads``
    of ``indexer_head_dim``), ``kI = R_I(LayerNorm(W_kI h))`` (ONE key head),
    ``w = W_w h``, ``I[t, s] = size^-1/2 heads^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])``.
  - scope ``select``: ``S_t`` = the ``topk`` positions ``s <= t`` of ``t``'s document
    with the largest ``I[t, s]`` (all where there are fewer; ties to the lower
    position), exactly (ops/sparse_attention.py).
  - scope ``attention_core``: ``o[t, a] = sum_{s in S_t} softmax_{s in S_t}(q[t, a] .
    k[s, g(a)] / sqrt(head_dim)) v[s, g(a)]``; no gradient reaches ``I`` from here.
  - scope ``indexer_loss``: ``L_I = mean_t KL(P[t, .] || softmax_{s in S_t} I[t, .])``,
    ``P`` the mean over the query heads of the probabilities above, without
    gradient: only the indexer's four parameters learn from it.
  - then ``W_o``.
- ``F`` (scope ``moe``): ``s = softmax_float32(u W_gate)`` over ALL
  ``experts_total``; the ``num_experts_per_tok`` largest, their scores divided by
  their sum (``norm_topk_prob``); the sum over the picked experts HELD here of
  ``s_e E_e(u)`` (ops/moe.py), ``E_e`` a SiLU-gated MLP of ``moe_intermediate_size``.
- the balance loss, Qwen3-MoE's: over the tokens of all layers together
  ``experts x sum_i f_i P_i`` (ops/moe.py::global_balance_loss) x
  ``router_aux_loss_coef``; the loss is cross-entropy + that + ``indexer_loss_coef``
  x the layers' ``L_I`` summed.
- ``logits = RMSNorm(x_L) H^T`` (``H`` the untied head's rows held here).

Plain functions over a parameter tree as models/deepseek_v2.py: the top level
is the kind of parameter (``embed``, ``attention``, ``indexer``, ``router``,
``experts``, ``norms``, ``head``).  float32 parameters; ``config.dtype``
(bfloat16) activations and matmul operands, the indexer's projections and the
operands of its scores too; float32 norms, router, softmaxes, rotary angles,
index scores, thresholds, ``P``, the KL and the loss.  Every layer is recomputed
in the backward pass; of its inside the selection's thresholds (two integers a
query) are kept, and where the attention kernels run their output and
log-sum-exp (``lm_layers.layer_keeps``; no layer here calls ``gated_mlp``, so
nothing carries ``lm_layers.MLP_GATE_UP``).  Single device.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from batchai_retinanet_horovod_coco_tpu.models import lm_layers
from batchai_retinanet_horovod_coco_tpu.ops import moe, rope
from batchai_retinanet_horovod_coco_tpu.ops import sparse_attention as sparse


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    experts_total: int  # the router's width: the model's routed experts
    experts_held: tuple[int, ...]  # the ids of those this chip computes
    num_experts_per_tok: int
    indexer_num_heads: int
    indexer_head_dim: int
    indexer_topk: int
    mrope_section: tuple[int, ...] = (16, 24, 24)
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    router_aux_loss_coef: float = 0.001
    indexer_loss_coef: float = 1.0
    dtype: Any = jnp.bfloat16
    attention_q_block: int = 1024  # as GraniteHybridConfig's; the xla lowering's blocks of queries

    @property
    def indexer_sections(self) -> tuple[int, ...]:
        """The indexer's head has ``indexer_head_dim / head_dim`` of the main
        head's frequency pairs: its runs are the main head's, cut alike."""
        return tuple(s * self.indexer_head_dim // self.head_dim for s in self.mrope_section)

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "KeyeVL2Config":
        """From the keys of the published ``config.json``; refuses what this
        model does not compute rather than ignoring it.  ``num_experts`` counts
        the experts HELD; a cut configuration adds ``num_experts_total`` (the
        router's width) and ``experts_held`` (their ids), without which all are
        held."""
        want = {"hidden_act": "silu", "use_sliding_window": False, "mlp_only_layers": [], "decoder_sparse_step": 1,
                "tie_word_embeddings": False, "attention_bias": False, "norm_topk_prob": True}
        wrong = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
        scaling, sa = hf.get("rope_scaling") or {}, hf["sa_config"]
        if scaling.get("rope_type", scaling.get("type")) != "default":
            wrong["rope_scaling.rope_type"] = scaling.get("rope_type", scaling.get("type"))
        if sa["indexer_num_kv_heads"] != 1:
            wrong["sa_config.indexer_num_kv_heads"] = sa["indexer_num_kv_heads"]
        if wrong:
            raise ValueError(f"KeyeVL2 does not compute {wrong}; it computes {want} with default rotary positions in "
                             "sections and one key head for the indexer")
        held = tuple(hf.get("experts_held", range(hf["num_experts"])))
        total = hf.get("num_experts_total", hf["num_experts"])
        if len(held) != hf["num_experts"] or len(set(held)) != len(held) or not all(0 <= e < total for e in held):
            raise ValueError(f"experts_held {held} for num_experts {hf['num_experts']} of {total}")
        sections = tuple(scaling["mrope_section"])
        if 2 * sum(sections) != hf["head_dim"] or any(s * sa["indexer_head_dim"] % hf["head_dim"] for s in sections):
            raise ValueError(f"mrope_section {sections} for heads of {hf['head_dim']} and {sa['indexer_head_dim']}")
        keys = {f.name for f in dataclasses.fields(cls)} - {"experts_total", "experts_held", "dtype", "mrope_section"}
        given = {k: hf[k] for k in keys if k in hf}
        given.update(indexer_num_heads=sa["indexer_num_heads"], indexer_head_dim=sa["indexer_head_dim"],
                     indexer_topk=sa["topk"], mrope_section=sections)
        return cls(experts_total=total, experts_held=held, **{**given, **overrides})


# The CPU tests' and ``train.py lm-synthetic --model tiny-keye``'s: three layers at toy
# widths, 4 of 16 experts held, 3 a token; a query keeps 24 keys of a 64-token sequence.
TINY = KeyeVL2Config(
    vocab_size=128, hidden_size=64, moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, experts_total=16, experts_held=(0, 1, 2, 3), num_experts_per_tok=3,
    indexer_num_heads=4, indexer_head_dim=8, indexer_topk=24, mrope_section=(2, 2, 4), attention_q_block=32,
)

INIT_STD = 0.02  # not given by the published configuration's catalog copy: ``assumed`` in the benchmark's file


def init_params(config: KeyeVL2Config, rng: jax.Array) -> dict:
    d, hd = config.hidden_size, config.head_dim
    q, kv = config.num_attention_heads * hd, config.num_key_value_heads * hd
    ih, isz = config.indexer_num_heads, config.indexer_head_dim
    width, held = config.moe_intermediate_size, len(config.experts_held)

    def normal(key, shape):
        return INIT_STD * jax.random.normal(key, shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = iter(jax.random.split(rng, 2 + 10 * config.num_hidden_layers))
    params: dict = {"embed": {"embedding": normal(next(keys), (config.vocab_size, d))},
                    "attention": {}, "indexer": {}, "router": {}, "experts": {},
                    "norms": {"final": ones(d)}, "head": {"rows": normal(next(keys), (config.vocab_size, d))}}
    for i in range(config.num_hidden_layers):
        name = f"layer_{i}"
        params["attention"][name] = {
            "q": normal(next(keys), (d, q)), "k": normal(next(keys), (d, kv)), "v": normal(next(keys), (d, kv)),
            "o": normal(next(keys), (q, d)), "q_norm": ones(hd), "k_norm": ones(hd)}
        params["indexer"][name] = {
            "q": normal(next(keys), (d, ih * isz)), "k": normal(next(keys), (d, isz)),
            "k_norm_scale": ones(isz), "k_norm_bias": jnp.zeros((isz,), jnp.float32),
            "w": normal(next(keys), (d, ih))}
        params["router"][name] = {"gate": normal(next(keys), (d, config.experts_total))}
        params["experts"][name] = {"gate_up": normal(next(keys), (held, d, 2 * width)),
                                   "down": normal(next(keys), (held, width, d))}
        params["norms"][name] = {"attention": ones(d), "mlp": ones(d)}
    return params


def _operand(config, x):
    """An operand of a matmul with a weight outside the indexer, in ``config.dtype``."""
    return x.astype(config.dtype)


def _indexer_operand(config, x):
    """An operand of one of the indexer's three projections."""
    return x.astype(config.dtype)


def _score_operand(config, x):
    """An operand of the index scores' products: ``qI`` and ``kI`` after the rotation."""
    return x.astype(config.dtype)


def _cast(config):
    # bound late: the benchmark's controls replace this module's ``_operand``
    return lambda x: _operand(config, x)


def _matmul(config, x, w):
    return lm_layers.matmul(_cast(config), x, w)


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True) + eps)
    return (y * scale + bias).astype(x.dtype)


def text_positions(segment_ids):
    """The three rotary ids of text tokens: all the position in the document."""
    return jnp.broadcast_to(rope.document_positions(segment_ids), (3, *segment_ids.shape))


def _lowering(seq_len: int) -> str:
    return sparse.lowering(jax.default_backend(), seq_len)


def _detached(u):
    """What the indexer reads of the layer's input: its value, not its gradient."""
    return jax.lax.stop_gradient(u)


def _indexer(config, p, u, position_ids):
    """The indexer's three projections of ``u`` (which carries no gradient):
    ``qI`` (batch, T, heads, size) and ``kI`` (batch, T, size) rotated, ``w``
    (batch, T, heads) float32."""
    batch, t, _ = u.shape
    heads, size = config.indexer_num_heads, config.indexer_head_dim
    cast = lambda x: _indexer_operand(config, x)
    angles = rope.sectioned_angles(position_ids, rope.plain_inv_freq(size, config.rope_theta), config.indexer_sections)
    q = rope.apply_rotary_halves(lm_layers.matmul(cast, u, p["q"]).reshape(batch, t, heads, size), angles)
    k = _layer_norm(lm_layers.matmul(cast, u, p["k"]), p["k_norm_scale"], p["k_norm_bias"], config.rms_norm_eps)
    k = rope.apply_rotary_halves(k, angles)
    return _score_operand(config, q), _score_operand(config, k), lm_layers.matmul(cast, u, p["w"]).astype(jnp.float32)


def _attention(config, with_selection, p, indexer, u, segment_ids, position_ids):
    """-> (``W_o o`` (batch, T, d), (``L_I``, the pairs selected, the queries
    whose threshold ties were cut), the selection (batch, T, T) bool or nothing)."""
    batch, t, _ = u.shape
    hd = config.head_dim
    angles = rope.sectioned_angles(position_ids, rope.plain_inv_freq(hd, config.rope_theta), config.mrope_section)
    q = _matmul(config, u, p["q"]).reshape(batch, t, config.num_attention_heads, hd)
    k = _matmul(config, u, p["k"]).reshape(batch, t, config.num_key_value_heads, hd)
    v = _matmul(config, u, p["v"]).reshape(batch, t, config.num_key_value_heads, hd)
    q = rope.apply_rotary_halves(lm_layers.rms_norm(q, p["q_norm"], config.rms_norm_eps), angles)
    k = rope.apply_rotary_halves(lm_layers.rms_norm(k, p["k_norm"], config.rms_norm_eps), angles)
    with jax.named_scope("indexer"):
        q_idx, k_idx, w = _indexer(config, indexer, _detached(u), position_ids)
    a = sparse.sparse_attention(
        q, k, v, q_idx, k_idx, w, segment_ids, topk=config.indexer_topk, scale=hd ** -0.5,
        index_scale=config.indexer_head_dim ** -0.5 * config.indexer_num_heads ** -0.5, how=_lowering(t),
        q_block=config.attention_q_block, with_mask=with_selection)
    return _matmul(config, a.out.reshape(batch, t, -1), p["o"]), (a.kl, a.selected, a.tied), a.mask


def _moe_lowering(config, batch: int, t: int) -> str:
    return moe.lowering(jax.default_backend(), batch * t * config.num_experts_per_tok, config.hidden_size,
                        config.moe_intermediate_size)


def _moe(config, router, experts, u):
    """-> (F(u) in ``u``'s dtype, (every expert's picks, every expert's mean
    score, the rows routed here by held expert, the picks (batch, T, k)))."""
    batch, t, _ = u.shape
    k = config.num_experts_per_tok
    routed, routing, plan = moe.expert_layer(
        u, router["gate"], _operand(config, experts["gate_up"]), _operand(config, experts["down"]),
        config.experts_held, k, _moe_lowering(config, batch, t), router=moe.route_renormalised)
    with jax.named_scope("aux"):
        mean_scores = jnp.mean(routing.scores, axis=0)
    return routed.astype(u.dtype), (routing.counts, mean_scores, plan.group_sizes, routing.picks.reshape(batch, t, k))


def _layer(config, with_selection: bool, attn_p, indexer_p, router_p, experts_p, norms, x, segment_ids, position_ids):
    with jax.named_scope("attention"):
        u = lm_layers.rms_norm(x, norms["attention"], config.rms_norm_eps)
        a, counters, mask = _attention(config, with_selection, attn_p, indexer_p, u, segment_ids, position_ids)
        h = x + a.astype(x.dtype)
    with jax.named_scope("moe"):
        u = lm_layers.rms_norm(h, norms["mlp"], config.rms_norm_eps)
        f, routed = _moe(config, router_p, experts_p, u)
    return h + f, counters, routed, mask


def hidden_states(config: KeyeVL2Config, params: dict, tokens, segment_ids, position_ids=None,
                  with_selection: bool = False):
    """``(x, aux)``: the last layer's output before the final norm (batch, T,
    d), and by layer (stacked on a leading axis) ``kl`` the indexer's loss,
    ``selected`` the pairs selected, ``tied`` the queries whose ties were cut,
    ``counts`` and ``mean_scores`` (experts) the router's, ``rows`` (held) the
    rows routed here, ``picks`` (batch, T, k); with ``with_selection`` also
    ``selection`` (batch, T, T) bool.  ``position_ids`` (3, batch, T): the
    temporal, height and width id of every token (text: its position in its
    document, the default)."""
    with jax.named_scope("embed"):
        x = lm_layers.embed_lookup(params["embed"]["embedding"], tokens, config.dtype)
        if position_ids is None:
            position_ids = text_positions(segment_ids)
    layer = jax.checkpoint(_layer, static_argnums=(0, 1), policy=lm_layers.policy(lm_layers.NO_PRODUCT))
    by_layer = []
    for i in range(config.num_hidden_layers):
        name = f"layer_{i}"
        x, counters, routed, mask = layer(
            config, with_selection, params["attention"][name], params["indexer"][name], params["router"][name],
            params["experts"][name], params["norms"][name], x, segment_ids, position_ids)
        by_layer.append((*counters, *routed, *((mask,) if with_selection else ())))
    names = ("kl", "selected", "tied", "counts", "mean_scores", "rows", "picks", "selection")
    return x, {n: jnp.stack(v) for n, v in zip(names, zip(*by_layer))}


def logits_of(config: KeyeVL2Config, params: dict, hidden):
    """float32 logits over the rows of the head held here."""
    with jax.named_scope("lm_head"):
        x = lm_layers.rms_norm(hidden, params["norms"]["final"], config.rms_norm_eps)
        return lm_layers.head_logits(_cast(config), x, params["head"]["rows"])


def causal_pairs(segment_ids):
    """The (query, key) pairs ``s <= t`` of one document in ``segment_ids``
    (batch, T), float32: what a selection is a share of."""
    p = rope.document_positions(segment_ids).astype(jnp.float32)
    return jnp.sum(p + 1.0)


class KeyeVL2:
    """The model as the train state and the loop hold it (as
    models/deepseek_v2.py::DeepseekV2)."""

    # the STEP_SCOPES (train/step.py, with what lies beneath each) a step of this model enters
    scopes = ("embed", "attention", "moe", "lm_head", "loss")

    def __init__(self, config: KeyeVL2Config):
        self.config = config

    def init(self, rng: jax.Array, tokens=None) -> dict:
        del tokens  # the parameters do not depend on the sequence's length
        return {"params": init_params(self.config, rng)}

    def describe(self) -> str:
        c = self.config
        return (f"keye-vl2 language model, {c.num_hidden_layers} layers, top-{c.indexer_topk} keys a query by "
                f"{c.indexer_num_heads} indexer heads, {len(c.experts_held)} of {c.experts_total} experts held, "
                f"{c.num_experts_per_tok} a token")

    def apply(self, variables: dict, tokens, segment_ids, train: bool = False, position_ids=None):
        del train  # no dropout, no batch statistics
        params = variables["params"]
        return logits_of(self.config, params, hidden_states(self.config, params, tokens, segment_ids, position_ids)[0])

    def loss(self, params: dict, tokens, segment_ids):
        """``(loss, the step's scalars)`` as the language-model task
        (train/task.py::LMTask) differentiates and logs them: the next-token
        cross-entropy plus the balance loss plus the indexer's loss, and the
        selection's and the routing's counters."""
        config = self.config
        hidden, aux = hidden_states(config, params, tokens, segment_ids)
        logits = logits_of(config, params, hidden)
        with jax.named_scope("loss"):
            cross_entropy, counted = lm_layers.next_token_loss(logits, tokens, segment_ids)
            balance = config.router_aux_loss_coef * moe.global_balance_loss(
                aux["counts"], aux["mean_scores"], tokens.size)
            kl = config.indexer_loss_coef * jnp.sum(aux["kl"])
            loss = cross_entropy + balance + kl
            selected_share = jnp.mean(aux["selected"]) / causal_pairs(segment_ids)
        rows = aux["rows"]
        return loss, {"loss": loss, "tokens_counted": counted, "moe/aux_loss": balance, "moe/rows_held": jnp.sum(rows),
                      "moe/rows_max_expert": jnp.max(rows), "moe/rows_min_expert": jnp.min(rows),
                      "dsa/kl_loss": kl, "dsa/selected_share": selected_share,
                      "dsa/threshold_ties": jnp.sum(aux["tied"])}

    def picks_and_selection(self, params: dict, tokens, segment_ids):
        """``(picks (layers, batch, T, k), selection (layers, batch, T, T)
        bool)``: the experts every token picked and the keys every query
        selected, which the benchmark's check compares with its reference's."""
        aux = hidden_states(self.config, params, tokens, segment_ids, with_selection=True)[1]
        return aux["picks"], aux["selection"]

    def run_meta(self, bucket) -> dict[str, Any]:
        """Which lowering the step's sparse attention (ops/sparse_attention.py),
        its grouped products and the row movements around them (ops/moe.py)
        take (on the kernels: a recomputed layer keeps their output and
        log-sum-exp), the keys a query keeps and the share of the experts held."""
        config, backend = self.config, jax.default_backend()
        how = _lowering(bucket[1])
        return {"attention_lowering": how, "attention_block_skip": "causal",
                **({"attention_residuals": "kept"} if how == sparse.KERNEL else {}),
                **lm_layers.run_meta(lm_layers.NO_PRODUCT),
                "dsa_topk": config.indexer_topk,
                "moe_lowering": _moe_lowering(config, *bucket),
                "moe_rows_lowering": moe.rows_lowering(backend, bucket[0] * bucket[1], config.num_experts_per_tok,
                                                       config.hidden_size, config.moe_intermediate_size),
                "experts_held": len(config.experts_held), "experts_total": config.experts_total}
