"""ms per step of device time in the latent-attention layers (norm, the four
projections, rotary, the blocked masked softmax): forward, recomputed forward
and backward; the device trace joined with the compiled step's scope ``mla``
(``harness/moe_lm_trace.py``)."""

from benchmark.harness import moe_lm_trace


def read(ctx):
    return moe_lm_trace.slice_ms(ctx, "mla")
