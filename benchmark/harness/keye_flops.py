"""Operations a Keye-VL-2.0 train step needs on a chip that holds a share of
the routed experts, and what its three kernel families are held to.

EVERY COUNT HERE IS THE MODEL'S, NOT THE IMPLEMENTATION'S: the main attention
over the SELECTED pairs only (a query at position p of its document attends to
``min(p + 1, topk)`` keys), the index scores over the causal pairs of one
document (the indexer must score every key a query may select), whatever
kernel, mask or blocking computes them.  A first version that forms whole
blocks under a dense mask reads low against these counts; a later kernel that
skips what was not selected reads higher; neither can read over 100%, and a
change of the implementation does not make the count stale.

``train_flops_per_step``: counted from the published configuration's keys as
``harness/moe_lm_flops.py`` counts DeepSeek-V2's: 2 per multiply-accumulate,
forward once and backward twice, NOTHING recomputed.  Matrix products with
parameters outside the routed experts x the step's tokens (attention's four
projections, the indexer's three, the router); the main attention's scores
and values over the selected pairs (128 wide each); the index scores over the
causal pairs (16 heads of 64); the routed experts' three products x THE ROWS
ACTUALLY ROUTED HERE (the step's ``moe/rows_held`` counter, summed over the
layers); the untied head over the rows of the vocabulary held here.  Norms,
activations, rotations, softmaxes, the selection itself (comparisons, no
multiply-accumulate), the KL, the sort and the gathers are left out.

``gmm_cost_per_step``, ``attention_core_cost_per_step``, ``indexer_cost_per_step``:
``{"ops", "bytes"}`` of a part over the passes the step runs, as
``moe_lm_flops.gmm_cost_per_step`` counts the grouped products: forward,
forward again (the layer is recomputed) and the two gradients of every product:
4 x the forward's operations; bytes are every pass's operands and results once
at the operands' width.  The indexer's part is what its scope holds: the three
projections and the scores.
"""

from __future__ import annotations

import numpy as np

PASSES = 4  # forward, the recomputed forward, and a product's two gradients


def selected_pairs(segment_ids: list[np.ndarray], topk: int) -> float:
    """Mean over the pool's sequences of the (query, key) pairs the main
    attention needs: sum over tokens of min(position in its document + 1, topk)."""
    pairs = []
    for batch in segment_ids:
        for row in np.asarray(batch):
            starts = np.flatnonzero(np.concatenate([[True], row[1:] != row[:-1]]))
            position = np.arange(len(row)) - np.repeat(starts, np.diff(np.append(starts, len(row))))
            pairs.append(float(np.sum(np.minimum(position + 1, topk))))
    return float(np.mean(pairs))


def _sizes(config: dict) -> dict:
    sa = config["sa_config"]
    return dict(d=config["hidden_size"], layers=config["num_hidden_layers"], heads=config["num_attention_heads"],
                q=config["num_attention_heads"] * config["head_dim"], kv=config["num_key_value_heads"] * config["head_dim"],
                hd=config["head_dim"], ih=sa["indexer_num_heads"], isz=sa["indexer_head_dim"],
                width=config["moe_intermediate_size"], held=config["num_experts"],
                experts_total=config.get("num_experts_total", config["num_experts"]))


def forward_flops_per_step(config: dict, tokens: int, selected: float, causal: float, rows_held: float) -> dict:
    """Forward FLOPs of one step of ``tokens`` tokens by part; ``selected`` the
    step's selected (query, key) pairs, ``causal`` its causal pairs of one
    document, ``rows_held`` its routed rows summed over the layers."""
    s = _sizes(config)
    d, layers = s["d"], s["layers"]
    out = {
        "attention_matmuls": 2.0 * tokens * layers * (2 * d * s["q"] + 2 * d * s["kv"]),
        "attention_pairs": 2.0 * selected * layers * s["heads"] * 2 * s["hd"],
        "indexer_matmuls": 2.0 * tokens * layers * d * (s["ih"] * s["isz"] + s["isz"] + s["ih"]),
        "index_scores": 2.0 * causal * layers * s["ih"] * s["isz"],
        "router": 2.0 * tokens * layers * d * s["experts_total"],
        "routed_experts": 2.0 * rows_held * 3 * d * s["width"],
        "lm_head": 2.0 * tokens * config["vocab_size"] * d,
    }
    out["total"] = sum(out.values())
    return out


def train_flops_per_step(config: dict, tokens: int, selected: float, causal: float, rows_held: float) -> dict:
    return {k: 3.0 * v for k, v in forward_flops_per_step(config, tokens, selected, causal, rows_held).items()}


def gmm_cost_per_step(config: dict, rows_held: float) -> dict:
    """The grouped products' calls in one step.  ``rows_held`` summed over the
    layers; the buffer is neither read nor written behind the routed rows."""
    s = _sizes(config)
    d, width = s["d"], s["width"]
    bf16 = 2
    calls = 0.0
    for k, n in ((d, 2 * width), (width, d)):  # gate and up in one product, then down
        lhs, out, weights = rows_held * k * bf16, rows_held * n * bf16, s["layers"] * s["held"] * k * n * bf16
        calls += 2 * (lhs + weights + out)  # forward, and again when the layer is recomputed
        calls += out + weights + lhs  # the gradient of the rows
        calls += lhs + out + weights  # the gradient of the weights
    return {"ops": PASSES * 2.0 * rows_held * 3 * d * width, "bytes": calls}


def attention_core_cost_per_step(config: dict, tokens: int, selected: float) -> dict:
    """The main attention over the selected pairs: scores and values."""
    s = _sizes(config)
    bf16 = 2
    a_pass = s["layers"] * tokens * (2 * s["q"] + 2 * s["kv"]) * bf16  # q, k, v in and the output out (or their gradients)
    return {"ops": PASSES * 2.0 * selected * s["layers"] * s["heads"] * 2 * s["hd"], "bytes": PASSES * a_pass}


def indexer_cost_per_step(config: dict, tokens: int, causal: float) -> dict:
    """The indexer's scope: its three projections and the scores over the causal pairs."""
    s = _sizes(config)
    columns = s["ih"] * s["isz"] + s["isz"] + s["ih"]
    bf16, f32 = 2, 4
    ops = PASSES * 2.0 * s["layers"] * (tokens * s["d"] * columns + causal * s["ih"] * s["isz"])
    a_pass = s["layers"] * (tokens * (s["d"] + columns) * bf16 + s["d"] * columns * f32 + causal * f32)  # the scores once
    return {"ops": ops, "bytes": PASSES * a_pass}
