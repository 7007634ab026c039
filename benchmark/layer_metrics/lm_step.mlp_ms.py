"""ms per step of device time in the gated MLPs (norm, gate/up, down):
forward, recomputed forward and backward; the device trace joined with the
compiled step's scope ``mlp`` (``harness/lm_trace.py``)."""

from benchmark.harness import lm_trace


def read(ctx):
    return lm_trace.slice_ms(ctx, "mlp")
