"""ms per step of device time in what routing costs around the experts'
products: the router (float32 softmax scores, top-8, the renormalised weights),
``dispatch`` (sort, group sizes, the rows into the buffer) and ``combine`` (the
rows back, weighed and added); forward, recomputed forward and backward; scopes
``moe/{router,dispatch,combine}`` (``harness/keye_trace.py``)."""

from benchmark.harness import keye_trace


def read(ctx):
    return keye_trace.slice_ms(ctx, "moe", ("router", "dispatch", "combine"))
