"""ms per step of device time in the chunked state-space scan with grouped B
and C (``ops/ssd.py``; on a TPU the kernel pair of ``ops/pallas/ssd.py`` beside
softplus, the cumulative sums and ``D x``): forward, recomputed forward and
backward; scope ``mamba/ssd`` (``harness/nemotron_trace.py``)."""

from benchmark.harness import nemotron_trace


def read(ctx):
    return nemotron_trace.slice_ms(ctx, "mamba", ("ssd",))
