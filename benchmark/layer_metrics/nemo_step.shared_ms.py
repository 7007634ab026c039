"""ms per step of device time in the shared squared-ReLU expert and its sum
with the routed part: forward, recomputed forward and backward; scope
``moe/shared`` (``harness/nemotron_trace.py``)."""

from benchmark.harness import nemotron_trace


def read(ctx):
    return nemotron_trace.slice_ms(ctx, "moe", ("shared",))
