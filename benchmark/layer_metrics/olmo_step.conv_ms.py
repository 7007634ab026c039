"""ms per step of device time in the depthwise causal convolution over the q, k
and v streams (11 520 channels a layer, 4 taps, blind across a document's start),
its SiLU and the two L2 norms: forward, recomputed forward and backward; scope
``gdn/conv`` (``harness/olmo_trace.py``)."""

from benchmark.harness import olmo_trace


def read(ctx):
    return olmo_trace.slice_ms(ctx, "gdn", ("conv",))
