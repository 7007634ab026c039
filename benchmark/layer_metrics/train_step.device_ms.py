"""ms per step in which an operation ran on the device: per run of the
train-step program in the traced window, the union of its device-operation
intervals; median over steps and devices."""

from benchmark.harness import trace_reduce as tr


def read(ctx):
    if ctx.trace is None:
        return None
    return tr.median(tr.per_module_busy_ms(ctx.trace, ctx.module_pattern(), ctx.window))
