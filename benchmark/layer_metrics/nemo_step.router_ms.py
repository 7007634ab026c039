"""ms per step of device time in what routing costs around the experts'
products: the router (float32 sigmoid scores, the biased top-k, the
normalised weights), ``dispatch`` (sort, group sizes, the rows into the
buffer) and ``combine`` (the rows back, weighed and added); forward,
recomputed forward and backward; scopes ``moe/{router,dispatch,combine}``
(``harness/nemotron_trace.py``)."""

from benchmark.harness import nemotron_trace


def read(ctx):
    return nemotron_trace.slice_ms(ctx, "moe", ("router", "dispatch", "combine"))
