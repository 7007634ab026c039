"""ms per step of device time in the gated MLPs (three products of 3840 x 11008
a layer and the norm of their output): forward, recomputed forward and backward;
scope ``mlp`` (``harness/olmo_trace.py``)."""

from benchmark.harness import olmo_trace


def read(ctx):
    return olmo_trace.slice_ms(ctx, "mlp")
