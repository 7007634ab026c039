"""ms per step of device time in the attention layers of both kinds (their input
and output norms, q, k, v, the gate and o with the per-head norms and the sliding
layers' rotation, and the blocked softmax itself): forward, recomputed forward
and backward; scope ``attention`` (``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "attention")
