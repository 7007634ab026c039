"""ms per step of device time in the gated-delta-rule mixers (the six projections,
the convolution, the L2 norms, the delta rule, the gated per-head norm, the output
projection and the norm of the mixer's output): forward, recomputed forward and
backward; the device trace joined with the compiled step's scope ``gdn``
(``harness/olmo_trace.py``)."""

from benchmark.harness import olmo_trace


def read(ctx):
    return olmo_trace.slice_ms(ctx, "gdn")
