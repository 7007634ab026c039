"""ms per step of device time in the Mamba-2 mixers (norm, in_proj, conv,
scan, gated norm, out_proj): forward, recomputed forward and backward; the
device trace joined with the compiled step's scope ``mamba``
(``harness/lm_trace.py``)."""

from benchmark.harness import lm_trace


def read(ctx):
    return lm_trace.slice_ms(ctx, "mamba")
