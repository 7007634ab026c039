"""ms per step of device time in the latent attention's blocked masked
softmax alone (``ops/attention.py::packed_causal_attention``, the code this
cell shares with the granite cell, where ``lm_step.attention_ms`` reads it):
forward, recomputed forward and backward, without the four projections and
rotary that ``moe_step.mla_ms`` adds; scope ``mla/core``
(``harness/moe_lm_trace.py``)."""

from benchmark.harness import moe_lm_trace


def read(ctx):
    return moe_lm_trace.slice_ms(ctx, "mla", ("core",))
