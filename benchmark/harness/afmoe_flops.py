"""Operations an ``afmoe`` (Trinity) train step needs on a chip that holds a
share of the routed experts, and what its two kernel families are held to.

EVERY COUNT HERE IS THE MODEL'S, NOT THE IMPLEMENTATION'S: attention over the
VISIBLE pairs only - in a full layer a query at position ``p`` of its document
sees ``p + 1`` keys, in a sliding layer ``min(p + 1, sliding_window)`` -,
whatever kernel, mask or blocking computes them.  A kernel that masks a window
without skipping does about four times the counted work at T = 16 384 and
reads low; one that runs only the window's block pairs reads higher; neither
can read over 100%, and a count of all causal pairs for the sliding layers
would read over the truth.

``train_flops_per_step``: counted from the published configuration's keys as
``harness/moe_lm_flops.py`` counts DeepSeek-V2's: 2 per multiply-accumulate,
forward once and backward twice, NOTHING recomputed.  Matrix products with
parameters outside the routed experts x the step's tokens (attention's five
projections: q, k, v, the gate and o; the dense layers' MLP; the router; the
shared expert); attention's scores and values over the visible pairs (128 wide
each); the routed experts' three products x THE ROWS ACTUALLY ROUTED HERE (the
step's ``moe/rows_held`` counter, summed over the expert layers); the untied
head over the rows of the vocabulary held here.  Norms, activations, rotations,
softmaxes, the gate's sigmoid, the sort and the gathers are left out.

``gmm_cost_per_step``, ``attention_cost_per_step``: ``{"ops", "bytes"}`` of a
part over the passes the step runs.  The grouped products as
``moe_lm_flops.gmm_cost_per_step`` counts dsv2's gated experts (gate and up in
one product, then down): forward, forward again (the layer is recomputed) and
the two gradients of every product, 4 x the forward's operations; bytes are
every pass's operands and results once at the operands' width.  Attention's
kernels run forward ONCE a layer and step (their output and log-sum-exp are
kept across the recomputation): two products a visible pair and head forward
(scores, values), four backward (dV, dP, dQ, dK); bytes are q, k, v in and the
output out, then those four and the output's gradient in and three gradients out.
"""

from __future__ import annotations

import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"
BF16 = 2


def visible_pairs(segment_ids: list[np.ndarray], window: int | None) -> float:
    """Mean over the pool's sequences of the (query, key) pairs one attention
    layer needs: sum over tokens of its position in its document + 1, and no
    more than ``window`` where there is one."""
    pairs = []
    for batch in segment_ids:
        for row in np.asarray(batch):
            starts = np.flatnonzero(np.concatenate([[True], row[1:] != row[:-1]]))
            seen = np.arange(len(row)) - np.repeat(starts, np.diff(np.append(starts, len(row)))) + 1
            pairs.append(float(np.sum(seen if window is None else np.minimum(seen, window))))
    return float(np.mean(pairs))


def _sizes(config: dict) -> dict:
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    return dict(d=config["hidden_size"], layers=config["num_hidden_layers"], dense=config["num_dense_layers"],
                expert_layers=config["num_hidden_layers"] - config["num_dense_layers"],
                sliding=kinds.count(SLIDING), full=kinds.count(FULL), heads=config["num_attention_heads"],
                q=config["num_attention_heads"] * config["head_dim"], kv=config["num_key_value_heads"] * config["head_dim"],
                hd=config["head_dim"], width=config["moe_intermediate_size"], held=config["num_experts"],
                experts_total=config.get("num_experts_total", config["num_experts"]))


def expert_layers(config: dict) -> int:
    return _sizes(config)["expert_layers"]


def forward_flops_per_step(config: dict, tokens: int, window_pairs: float, full_pairs: float, rows_held: float) -> dict:
    """Forward FLOPs of one step of ``tokens`` tokens by part; ``window_pairs``
    and ``full_pairs`` the step's visible (query, key) pairs in ONE layer of
    each kind, ``rows_held`` its routed rows summed over the expert layers."""
    s = _sizes(config)
    d = s["d"]
    out = {
        "attention_matmuls": 2.0 * tokens * s["layers"] * (3 * d * s["q"] + 2 * d * s["kv"]),
        "window_attention_pairs": 2.0 * window_pairs * s["sliding"] * s["heads"] * 2 * s["hd"],
        "full_attention_pairs": 2.0 * full_pairs * s["full"] * s["heads"] * 2 * s["hd"],
        "dense_mlp": 2.0 * tokens * s["dense"] * 3 * d * config["intermediate_size"],
        "router": 2.0 * tokens * s["expert_layers"] * d * s["experts_total"],
        "shared_experts": 2.0 * tokens * s["expert_layers"] * 3 * d * config["num_shared_experts"] * s["width"],
        "routed_experts": 2.0 * rows_held * 3 * d * s["width"],
        "lm_head": 2.0 * tokens * config["vocab_size"] * d,
    }
    out["total"] = sum(out.values())
    return out


def train_flops_per_step(config: dict, tokens: int, window_pairs: float, full_pairs: float, rows_held: float) -> dict:
    return {k: 3.0 * v for k, v in forward_flops_per_step(config, tokens, window_pairs, full_pairs, rows_held).items()}


def gmm_cost_per_step(config: dict, rows_held: float) -> dict:
    """The grouped products' calls in one step.  ``rows_held`` summed over the
    expert layers; the buffer is neither read nor written behind the routed rows."""
    s = _sizes(config)
    d, width = s["d"], s["width"]
    calls = 0.0
    for k, n in ((d, 2 * width), (width, d)):  # gate and up in one product, then down
        lhs, out, weights = rows_held * k * BF16, rows_held * n * BF16, s["expert_layers"] * s["held"] * k * n * BF16
        calls += 2 * (lhs + weights + out)  # forward, and again when the layer is recomputed
        calls += out + weights + lhs  # the gradient of the rows
        calls += lhs + out + weights  # the gradient of the weights
    return {"ops": 4 * 2.0 * rows_held * 3 * d * width, "bytes": calls}


def attention_cost_per_step(config: dict, tokens: int, pairs: float, layers: int) -> dict:
    """``layers`` attention layers of one kind over ``pairs`` visible pairs each:
    six products a pair and head (two forward, four backward)."""
    s = _sizes(config)
    forward = tokens * (2 * s["q"] + 2 * s["kv"]) * BF16  # q, k, v in, the output out
    backward = tokens * (3 * s["q"] + 2 * s["kv"]) * BF16 + tokens * (s["q"] + 2 * s["kv"]) * BF16  # + dO in; dq, dk, dv out
    return {"ops": 6 * 2.0 * pairs * layers * s["heads"] * s["hd"], "bytes": float(layers * (forward + backward))}
