"""ms per step of device time in the shared experts' gated MLP (and the sum
with the routed part): forward, recomputed forward and backward; scope
``moe/shared`` (``harness/moe_lm_trace.py``)."""

from benchmark.harness import moe_lm_trace


def read(ctx):
    return moe_lm_trace.slice_ms(ctx, "moe", ("shared",))
