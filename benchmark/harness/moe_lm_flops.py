"""Operations a DeepSeek-V2 train step needs on a chip that holds a share of
the routed experts, and what the grouped-matmul kernel executes.

``train_flops_per_step``: counted from the published configuration's keys as
``harness/lm_flops.py`` counts granite's: 2 per multiply-accumulate, forward
once and backward twice, NOTHING recomputed.  Matrix products with
parameters outside the routed experts x the step's tokens; attention's
scores and values over the pairs the traffic really has (a query and an
earlier token of the same document: 192 wide for the scores, 128 for the
values); the routed experts' three products x THE ROWS ACTUALLY ROUTED HERE
(the step's ``moe/rows_held`` counter, summed over the expert layers: a
token's pick of an absent expert costs this chip nothing and is not counted);
the head over the rows of the vocabulary held here.  Norms, activations,
rotary, softmaxes, the sort and the gathers are left out.

``gmm_cost_per_step``: what the kernel (``ops/moe.py``: megablox ``gmm`` and
``tgmm``) runs in one step, for its roofline share: every product forward,
forward again (the layer is recomputed in the backward pass), and its two
gradients: 4 x the forward's operations on the routed rows; bytes are each
call's operands and result once (bfloat16).
"""

from __future__ import annotations


def _expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def forward_flops_per_step(config: dict, tokens: int, pairs: float, rows_held: float) -> dict:
    """Forward FLOPs of one step of ``tokens`` tokens by part; ``pairs`` the
    step's (query, key) pairs, ``rows_held`` its routed rows summed over the
    expert layers."""
    d, heads, layers = config["hidden_size"], config["num_attention_heads"], config["num_hidden_layers"]
    qk, vd, rank = config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"], config["kv_lora_rank"]
    width, experts_total = config["moe_intermediate_size"], config.get("n_routed_experts_total", config["n_routed_experts"])
    moe_layers = _expert_layers(config)
    attention = d * heads * qk + d * (rank + config["qk_rope_head_dim"]) + rank * heads * (
        config["qk_nope_head_dim"] + vd) + heads * vd * d
    out = {
        "attention_matmuls": 2.0 * tokens * layers * attention,
        "attention_pairs": 2.0 * pairs * layers * heads * (qk + vd),
        "dense_mlp": 2.0 * tokens * config["first_k_dense_replace"] * 3 * d * config["intermediate_size"],
        "router": 2.0 * tokens * moe_layers * d * experts_total,
        "shared_experts": 2.0 * tokens * moe_layers * 3 * d * config["n_shared_experts"] * width,
        "routed_experts": 2.0 * rows_held * 3 * d * width,
        "lm_head": 2.0 * tokens * config["vocab_size"] * d,
    }
    out["total"] = sum(out.values())
    return out


def train_flops_per_step(config: dict, tokens: int, pairs: float, rows_held: float) -> dict:
    return {k: 3.0 * v for k, v in forward_flops_per_step(config, tokens, pairs, rows_held).items()}


def gmm_cost_per_step(config: dict, rows_held: float) -> dict:
    """``{"ops", "bytes"}`` of the kernel's calls in one step.  ``rows_held``
    summed over the expert layers; the buffer is neither read nor written
    behind the routed rows, so its size does not enter."""
    d, width, held = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    ops = 4 * 2.0 * rows_held * 3 * d * width
    bf16 = 2
    calls = 0.0
    for k, n in ((d, 2 * width), (width, d)):  # gate and up in one product, then down
        lhs, out, weights = rows_held * k * bf16, rows_held * n * bf16, _expert_layers(config) * held * k * n * bf16
        calls += 2 * (lhs + weights + out)  # forward, and again when the layer is recomputed
        calls += out + weights + lhs  # the gradient of the rows
        calls += lhs + out + weights  # the gradient of the weights
    return {"ops": ops, "bytes": calls}
