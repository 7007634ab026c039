"""Operations an Olmo-Hybrid train step needs, from shapes alone, and what its
delta-rule kernels have to do.

``train_flops_per_sequence``: counted from the published configuration's keys as
``harness/lm_flops.py`` counts granite's: 2 per multiply-accumulate, the forward
once and the backward twice (input and weight gradients), NOTHING recomputed.
Matrix products with parameters (a linear-attention layer's six projections in
and one out, a full layer's four, the MLPs' three, the untied head over the
rows of the vocabulary held here), attention's scores and values over the pairs
the traffic really has (a query and an earlier token of the same document), and
the gated delta rule as ``delta_rule_forward_ops`` counts it.  Norms,
activations, the L2 norms, the 4-tap convolution's 8 operations a channel and
the embedding lookup are left out (under 0.1%).

``delta_rule_forward_ops``: the MINIMAL chunked algorithm at the program's chunk of
``C`` tokens, per chunk and head (key size K, value size V): ``K K^T`` and ``Q K^T``
(C x C x K each), the unit-lower-triangular system solved against the values
(C x C x V: a forward substitution does half of that square, a masked product the
whole; the whole is counted, as ``nemotron_flops.ssd_cost_per_step`` counts its
masked products), the weights times the corrected values (C x C x V), and the
carried state's three products ``K S^T``, ``Q S^T`` and ``U^T K`` (C x K x V each):
``2 C (2 C K + 2 C V + 3 K V)`` operations.  The inverse the kernels form (twelve
(C, C, C) products in three passes each: ops/pallas/delta_rule.py), the decays
and the masks are the implementation's and are NOT counted.

``delta_rule_cost_per_step``: ``{"ops", "bytes"}`` for ``olmo_delta_rule_roofline``:
every product once for the forward and once for each of its two gradients (3 x
the forward's operations), NEVER for the recomputation (the program runs its
forward kernel twice a layer; the second run is its choice); bytes: q, k, v in
and o out at the operands' width, the two per-token scalars in float32, forward;
those, o's cotangent and the three gradients, the scalars' gradients, backward;
the chunks' states written once and read once in float32.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def _kinds(config: dict) -> tuple[int, int]:
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    return kinds.count(LINEAR), kinds.count(FULL)


def delta_rule_forward_ops(config: dict, tokens: int, chunk: int) -> float:
    """Of ONE linear-attention layer's delta rule over ``tokens`` tokens."""
    heads, k, v = config["linear_num_value_heads"], config["linear_key_head_dim"], config["linear_value_head_dim"]
    return 2.0 * tokens * heads * (2 * chunk * k + 2 * chunk * v + 3 * k * v)


def forward_flops_per_sequence(config: dict, seq_len: int, pairs: float, chunk: int) -> dict:
    """Forward FLOPs of one sequence of ``seq_len`` tokens by part."""
    d, ff, vocab = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    heads = config["linear_num_value_heads"]
    kd, vd = heads * config["linear_key_head_dim"], heads * config["linear_value_head_dim"]
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    n_linear, n_full = _kinds(config)
    out = {
        "gdn_matmuls": 2.0 * seq_len * n_linear * (d * (2 * kd + 2 * vd + 2 * heads) + vd * d),
        "delta_rule": n_linear * delta_rule_forward_ops(config, seq_len, chunk),
        "attention_matmuls": 2.0 * seq_len * n_full * (2 * d * d + 2 * d * kv),
        "attention_pairs": 2.0 * pairs * n_full * 2 * d,  # scores and values, d = heads x head size
        "mlp": 2.0 * seq_len * (n_linear + n_full) * 3 * d * ff,
        "lm_head": 2.0 * seq_len * vocab * d,
    }
    out["total"] = sum(out.values())
    return out


def train_flops_per_sequence(config: dict, seq_len: int, pairs: float, chunk: int) -> dict:
    return {k: 3.0 * v for k, v in forward_flops_per_sequence(config, seq_len, pairs, chunk).items()}


def delta_rule_cost_per_step(config: dict, tokens: int, chunk: int) -> dict:
    """``{"ops", "bytes"}`` of the delta rules of one step's linear-attention layers
    (forward and the two gradients of every product; no recomputation)."""
    heads, k, v = config["linear_num_value_heads"], config["linear_key_head_dim"], config["linear_value_head_dim"]
    layers = _kinds(config)[0]
    bf16, f32 = 2, 4
    qkv = tokens * heads * (2 * k + v) * bf16  # as their three gradients
    out = tokens * heads * v * bf16  # o, as its cotangent
    scalars = 2 * tokens * heads * f32  # the log-decay and b, or their gradients
    states = tokens / chunk * heads * k * v * f32
    forward = qkv + scalars + out + states
    backward = (qkv + scalars + out + states) + (qkv + scalars)
    return {"ops": layers * 3.0 * delta_rule_forward_ops(config, tokens, chunk), "bytes": layers * (forward + backward)}
