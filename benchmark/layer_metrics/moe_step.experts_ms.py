"""ms per step of device time in the routed experts' grouped products and
what lies between them (the weights' casts, silu x up, the masks, the
transposes the weights' gradient reads): forward, recomputed forward and
backward; scope ``moe/experts`` (``harness/moe_lm_trace.py``)."""

from benchmark.harness import moe_lm_trace


def read(ctx):
    return moe_lm_trace.slice_ms(ctx, "moe", ("experts",))
