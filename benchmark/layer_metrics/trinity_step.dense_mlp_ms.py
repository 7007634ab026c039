"""ms per step of device time in the leading dense layers' gated MLP with its input
and output norms: forward, recomputed forward and backward; scope ``dense_mlp``
(``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "dense_mlp")
