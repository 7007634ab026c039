"""ms per step of device time in the chunked state-space scan
(``ops/ssd.py``, scope ``mamba/ssd``): forward, recomputed forward and
backward; the device trace joined with the compiled step's scopes
(``harness/lm_trace.py``)."""

from benchmark.harness import lm_trace


def read(ctx):
    return lm_trace.slice_ms(ctx, "mamba", beneath="ssd")
