"""ms per step of device time in the routed experts (the two grouped products of
the SiLU-gated expert, its activation, the casts of the held weights and the
masks behind the routed rows): forward, recomputed forward and backward; scope
``moe/experts`` (``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "moe", ("experts",))
