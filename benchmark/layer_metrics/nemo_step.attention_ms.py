"""ms per step of device time in the grouped-query attention layer (norm, q, k,
v, o and the causal same-document softmax of ``ops/attention.py``): forward,
recomputed forward and backward; scope ``attention``
(``harness/nemotron_trace.py``)."""

from benchmark.harness import nemotron_trace


def read(ctx):
    return nemotron_trace.slice_ms(ctx, "attention")
