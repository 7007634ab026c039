"""Operations a language-model train step needs, from shapes alone.

Counted from the published configuration's keys: 2 per multiply-accumulate,
the forward once and the backward twice (input and weight gradients),
NOTHING recomputed (the program recomputes every layer in its backward pass;
that work is not the model's).  Matrix multiplications with parameters, the
tied head over the vocabulary held here, attention's scores and values over
the pairs the traffic really has (a query and an earlier token of the same
document), and the state-space recurrence as the recurrence (per token and
head, ``S = a S + dt X (x) B`` is 3 P N operations and ``Y = S C`` is 2 P N;
the chunked form the program runs does other work).  Norms, activations,
the 4-tap convolution's 8 operations per channel and the embedding lookup
are left out (under 0.1%).
"""

from __future__ import annotations

import numpy as np


def attention_pairs(segment_ids: list[np.ndarray]) -> float:
    """Mean over the pool's sequences of the (query, key) pairs attention
    needs: sum over documents of L (L + 1) / 2."""
    pairs = []
    for batch in segment_ids:
        for row in np.asarray(batch):
            lengths = np.diff(np.flatnonzero(np.concatenate([[True], row[1:] != row[:-1], [True]])))
            pairs.append(float(np.sum(lengths * (lengths + 1) / 2)))
    return float(np.mean(pairs))


def forward_flops_per_sequence(config: dict, seq_len: int, pairs: float) -> dict:
    """Forward FLOPs of one sequence of ``seq_len`` tokens by part."""
    d, ff, vocab = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    heads, hd, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    inner = heads * hd
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    out = {
        "mamba_matmuls": 2.0 * seq_len * n_mamba * (d * (2 * inner + 2 * n + heads) + inner * d),
        "ssd": 5.0 * seq_len * n_mamba * heads * hd * n,
        "attention_matmuls": 2.0 * seq_len * n_attn * (2 * d * d + 2 * d * kv),
        "attention_pairs": 2.0 * pairs * n_attn * 2 * d,  # scores and values, d = heads x head size
        "mlp": 2.0 * seq_len * len(kinds) * 3 * d * ff,
        "lm_head": 2.0 * seq_len * vocab * d,
    }
    out["total"] = sum(out.values())
    return out


def train_flops_per_sequence(config: dict, seq_len: int, pairs: float) -> dict:
    return {k: 3.0 * v for k, v in forward_flops_per_sequence(config, seq_len, pairs).items()}
