"""ms per step of device time in the routed experts (the two grouped products
of the squared-ReLU expert, its activation, the casts of the held weights and
the masks behind the routed rows): forward, recomputed forward and backward;
scope ``moe/experts`` (``harness/nemotron_trace.py``)."""

from benchmark.harness import nemotron_trace


def read(ctx):
    return nemotron_trace.slice_ms(ctx, "moe", ("experts",))
