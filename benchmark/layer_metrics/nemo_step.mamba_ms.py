"""ms per step of device time in the Mamba-2 layers (their norm, ``in_proj``,
``conv``, ``ssd``, ``gate_norm``, ``out_proj``): forward, recomputed forward and
backward; scope ``mamba`` (``harness/nemotron_trace.py``)."""

from benchmark.harness import nemotron_trace


def read(ctx):
    return nemotron_trace.slice_ms(ctx, "mamba")
