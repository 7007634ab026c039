"""ms per step of device time in what routing costs around the experts'
products: the router (float32 scores, top-k), ``dispatch`` (sort, group
sizes, the gather into the buffer), ``combine`` (the gather back and the
weighted sum) and the balance loss; forward, recomputed forward and backward;
scopes ``moe/{router,dispatch,combine,aux}`` (``harness/moe_lm_trace.py``)."""

from benchmark.harness import moe_lm_trace


def read(ctx):
    return moe_lm_trace.slice_ms(ctx, "moe", ("router", "dispatch", "combine", "aux"))
