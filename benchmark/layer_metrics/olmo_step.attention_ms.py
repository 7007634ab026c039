"""ms per step of device time in the full-attention layer (four projections, the
q and k norms, the blocked attention kernels, the norm of the mixer's output):
forward, recomputed forward and backward; scope ``attention``
(``harness/olmo_trace.py``)."""

from benchmark.harness import olmo_trace


def read(ctx):
    return olmo_trace.slice_ms(ctx, "attention")
