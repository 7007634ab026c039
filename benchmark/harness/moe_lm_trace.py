"""The DeepSeek-V2 step's device time by named scope, for the ``moe_step.*``
readers, and its grouped-matmul kernel's time: ``harness/lm_trace.py``'s
join with this model's scopes.  Against a program that has no such step,
scopes or kernel every function here returns nothing and raises nothing."""

from __future__ import annotations

import numpy as np

from benchmark.harness import program_trace
from benchmark.harness import trace_reduce as tr

EXPECTED = {"mla", "moe", "dense_mlp", "lm_head", "optimizer"}
# The kernel's calls as the compiled step names them (ops/moe.py: megablox's
# ``gmm`` and ``tgmm`` pallas calls: ``gmm``, ``gmm.7``, ``tgmm.2``).
GMM_PATTERN = r"^t?gmm(\.\d+)?$"


def slices(ctx) -> dict | None:
    """``program_trace.join``'s result for this run (once per run), or nothing."""
    if not hasattr(ctx, "_program_slices"):
        try:
            from batchai_retinanet_horovod_coco_tpu.train import loop, step

            table, levels = step.scope_table(loop.compiled_step()), step.STEP_SCOPES
        except (ImportError, AttributeError, LookupError) as e:
            program_trace.say(f"no compiled step with scopes: {e!r}")
            table = None
        if table is None or not EXPECTED <= {t[0] for t in table.values()}:
            ctx._program_slices = None
        else:
            program_trace.slices(ctx, table, levels)
    return ctx._program_slices


def slice_ms(ctx, name: str, beneath: tuple[str, ...] | None = None) -> float | None:
    """ms per step in slice ``name`` (median over the steady runs), or in
    the scopes ``beneath`` it (forward, recomputed forward and backward, mean
    over the runs)."""
    s = slices(ctx)
    if s is None:
        return None
    if beneath is None:
        return s["ms"].get(name, 0.0)
    return sum(sum(s["by_scope"].get(name, {}).get(b, {}).values()) for b in beneath)


def gmm_ms_and_rows(ctx) -> tuple[float, float] | None:
    """Per step of the steady stretch, the mean time inside the
    grouped-matmul kernel's calls (ms) and the mean rows routed to the held
    experts (``moe/rows_held``, summed over the expert layers), or nothing
    where the step has no such kernel or counter.

    Routing moves as the model trains, so the rows must be those of the very
    steps whose kernel time is read.  The profiler starts when step
    ``facts["trace_from"]`` has been fetched, so the device plane's runs of the
    step program are steps ``trace_from + 1`` onward in order; the loop fetches
    the counter once per log window, and a step between two fetched steps gets
    the value interpolated between them (``facts["moe_rows_logged"]``)."""
    logged, trace_from = ctx.facts.get("moe_rows_logged"), ctx.facts.get("trace_from")
    if ctx.trace is None or not ctx.trace.devices or not logged or trace_from is None:
        return None
    device, pattern = ctx.trace.devices[0], ctx.module_pattern()
    runs = tr.module_events(device, pattern, None)
    inside = {(m.start, m.end) for m in tr.module_events(device, pattern, ctx.window)}
    steps = [trace_from + 1 + i for i, m in enumerate(runs) if (m.start, m.end) in inside]
    ms = tr.op_time_per_module_ms(ctx.trace, GMM_PATTERN, pattern, ctx.window)[: len(steps)]  # the first device's
    if not steps or not sum(ms):
        return None
    xs, ys = zip(*logged)
    if not xs[0] <= steps[0] <= steps[-1] <= xs[-1]:
        return None
    rows = np.interp(steps, xs, ys)
    return sum(ms) / len(ms), float(rows.mean())
