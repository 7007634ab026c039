"""The assignment kernel's share of its roofline: the least time the chip
could take (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from shapes in ``harness/flops.py``) over the kernel's time.
At the flagship shapes the bytes bound applies (77 MB against 5 GFLOP)."""

from benchmark.harness import flops
from benchmark.harness import trace_reduce as tr


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    ms = tr.median(tr.op_time_per_module_ms(
        ctx.trace, ctx.facts["assign_pattern"], ctx.module_pattern(), ctx.window))
    if not ms:
        return None
    return 100.0 * flops.roofline_share(ctx.facts["assign_cost"], ms / 1e3, ctx.peaks)["share"]
