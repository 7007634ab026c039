"""ms per step of device time in the selection: the exact ``topk``-th largest
score a query by bisection over the float's ordered bit pattern, the tie cut,
and the comparison that makes the (T, T) selection; forward and recomputed
forward (the thresholds are kept, so the recomputation is the comparison alone);
scope ``attention/select`` (``harness/keye_trace.py``)."""

from benchmark.harness import keye_trace


def read(ctx):
    return keye_trace.slice_ms(ctx, "attention", ("select",))
