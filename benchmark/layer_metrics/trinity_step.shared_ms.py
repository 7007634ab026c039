"""ms per step of device time in the shared expert's gated MLP (and the sum with
the routed part): forward, recomputed forward and backward; scope ``moe/shared``
(``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "moe", ("shared",))
