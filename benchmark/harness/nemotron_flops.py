"""Operations a Nemotron-H train step needs on a chip that holds a share of
the routed experts, and what its two kernel families execute.

``train_flops_per_step``: counted from the published configuration's keys as
``harness/lm_flops.py`` and ``harness/moe_lm_flops.py`` count theirs: 2 per
multiply-accumulate, forward once and backward twice, NOTHING recomputed.
Matrix products with parameters outside the routed experts x the step's
tokens (a layer is ONE mixer: a Mamba-2 layer's two projections, an attention
layer's four, an expert layer's router and shared expert); attention's scores
and values over the pairs the traffic really has; the state-space recurrence
as the recurrence (per token and head 5 P N operations); the routed experts'
TWO products (up, down: no gate) x THE ROWS ACTUALLY ROUTED HERE (the step's
``moe/rows_held`` counter, summed over the expert layers); the untied head
over the rows of the vocabulary held here.  Norms, activations, the
convolution, sigmoids, the sort and the gathers are left out.

``gmm_cost_per_step``: what the grouped products (``ops/moe.py``: megablox
``gmm`` and ``tgmm``) need in one step, for ``nemo_gmm_roofline``: two
products an expert, each forward, forward again (the layer is recomputed) and
its two gradients: 4 x the forward's operations on the routed rows; bytes are
each call's operands and result once (bfloat16).

``ssd_cost_per_step``: what the chunked scan needs between its operands and
its result, whatever implements it (``ops/pallas/ssd.py`` today), for
``nemo_ssd_roofline``.  Per chunk of L tokens, a group of B and C shared by
its heads, and a head (P channels, N state): the chunk's own term ``C B^T``
(L x L x N a GROUP), weights times values (L x L x P), the state's reach into
the chunk (L x N x P) and the chunk's state (L x N x P): ``2 L (L N / hpg + L
P + 2 N P)`` operations forward a head and chunk, ``hpg`` the heads a group.
That is the MINIMAL chunked algorithm at the program's own chunk: no causal
half is skipped (a masked matmul does the whole square) and the decays and
masks (elementwise, VPU) are not counted.  The backward pass needs twice the
forward's products (every product has two operands to differentiate; the
weights it forms again to do so are the implementation's choice and are not
counted); a step is forward + recomputed forward + backward = 4 x the
forward, as the grouped products are counted.  Bytes: every activation once
at the operands' width (x, B, C in and y out forward; those and y's
cotangent in, x's, B's and C's out backward), the per-token scalars (dt and
its cumulative sum, and their gradients) and the chunks' states (written once
by the forward that saves them, read once by the backward) in float32.  The
program's float32 ``y`` is its choice and costs more than is counted.  At
these shapes the two bounds lie side by side (6.3 ms of operations, 7.4 of
bytes a step of 16 384 tokens): the reader takes the larger, the bytes.
"""

from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _kinds(config: dict) -> tuple[int, int, int]:
    pattern = config["hybrid_override_pattern"][: config["num_hidden_layers"]]
    return pattern.count(MAMBA), pattern.count(EXPERTS), pattern.count(ATTENTION)


def expert_layers(config: dict) -> int:
    return _kinds(config)[1]


def forward_flops_per_step(config: dict, tokens: int, pairs: float, rows_held: float) -> dict:
    """Forward FLOPs of one step of ``tokens`` tokens by part; ``pairs`` the
    step's (query, key) pairs, ``rows_held`` its routed rows summed over the
    expert layers."""
    d = config["hidden_size"]
    heads, hd, n, groups = config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"], config["n_groups"]
    inner = heads * hd
    q, kv = config["num_attention_heads"] * config["head_dim"], config["num_key_value_heads"] * config["head_dim"]
    width, shared = config["moe_intermediate_size"], config["moe_shared_expert_intermediate_size"]
    experts_total = config.get("n_routed_experts_total", config["n_routed_experts"])
    n_mamba, n_moe, n_attn = _kinds(config)
    out = {
        "mamba_matmuls": 2.0 * tokens * n_mamba * (d * (2 * inner + 2 * groups * n + heads) + inner * d),
        "ssd": 5.0 * tokens * n_mamba * heads * hd * n,
        "attention_matmuls": 2.0 * tokens * n_attn * (2 * d * q + 2 * d * kv),
        "attention_pairs": 2.0 * pairs * n_attn * 2 * q,
        "router": 2.0 * tokens * n_moe * d * experts_total,
        "shared_experts": 2.0 * tokens * n_moe * 2 * d * shared,
        "routed_experts": 2.0 * rows_held * 2 * d * width,
        "lm_head": 2.0 * tokens * config["vocab_size"] * d,
    }
    out["total"] = sum(out.values())
    return out


def train_flops_per_step(config: dict, tokens: int, pairs: float, rows_held: float) -> dict:
    return {k: 3.0 * v for k, v in forward_flops_per_step(config, tokens, pairs, rows_held).items()}


def gmm_cost_per_step(config: dict, rows_held: float) -> dict:
    """``{"ops", "bytes"}`` of the grouped products' calls in one step.
    ``rows_held`` summed over the expert layers; the buffer is neither read
    nor written behind the routed rows, so its size does not enter."""
    d, width, held = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    ops = 4 * 2.0 * rows_held * 2 * d * width
    bf16 = 2
    calls = 0.0
    for k, n in ((d, width), (width, d)):  # up, then down
        lhs, out, weights = rows_held * k * bf16, rows_held * n * bf16, expert_layers(config) * held * k * n * bf16
        calls += 2 * (lhs + weights + out)  # forward, and again when the layer is recomputed
        calls += out + weights + lhs  # the gradient of the rows
        calls += lhs + out + weights  # the gradient of the weights
    return {"ops": ops, "bytes": calls}


def ssd_cost_per_step(config: dict, tokens: int, chunk: int) -> dict:
    """``{"ops", "bytes"}`` of the scans of one step's Mamba-2 layers (forward,
    recomputed forward, backward) at chunks of ``chunk`` tokens."""
    heads, p, n, groups = config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"], config["n_groups"]
    layers = _kinds(config)[0]
    forward_ops = 2.0 * tokens * heads * (chunk * n / (heads // groups) + chunk * p + 2 * n * p)
    bf16, f32 = 2, 4
    x = tokens * heads * p * bf16  # as y, dy and dx
    scalars = 2 * tokens * heads * f32  # dt and its cumulative sum, or their gradients
    bc = 2 * tokens * groups * n * bf16  # B and C, or their gradients
    states = tokens / chunk * heads * p * n * f32
    forward = (x + scalars + bc) + x
    backward = (x + scalars + bc + states + x) + (x + scalars + bc)
    return {"ops": layers * 4.0 * forward_ops, "bytes": layers * (2 * forward + states + backward)}
