"""ms per step of device time in what routing costs around the experts' products:
the router (float32 sigmoid scores, top-8 of score + bias, the normalised weights
times ``route_scale``), ``dispatch`` (sort, group sizes, the rows into the buffer)
and ``combine`` (the rows back, weighed and added); forward, recomputed forward
and backward; scopes ``moe/{router,dispatch,combine}`` (``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "moe", ("router", "dispatch", "combine"))
