"""ms per step in the fused anchor assignment (``ops/pallas/matching.py``):
device durations of the step's one Mosaic custom call, median over steps;
nothing where no operation of that name ran."""

from benchmark.harness import trace_reduce as tr


def read(ctx):
    if ctx.trace is None:
        return None
    per_step = tr.op_time_per_module_ms(
        ctx.trace, ctx.facts["assign_pattern"], ctx.module_pattern(), ctx.window)
    return tr.median(per_step) if any(per_step) else None
