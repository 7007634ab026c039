"""The Olmo-Hybrid step's device time by named scope, for the ``olmo_step.*``
readers, and the time inside its delta-rule kernels' calls: ``harness/lm_trace.py``'s
join with this model's scopes.  Against a program that has no such step, scopes or
kernels every function here returns nothing and raises nothing."""

from __future__ import annotations

from benchmark.harness import program_trace
from benchmark.harness import trace_reduce as tr

EXPECTED = {"gdn", "attention", "mlp", "lm_head", "optimizer"}
# The kernels' calls as the compiled step names them (ops/pallas/delta_rule.py: ``delta_rule_fwd``,
# ``delta_rule_bwd.3``).
DELTA_RULE_PATTERN = r"^delta_rule_(fwd|bwd)(\.\d+)?$"


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
    """ms per step in slice ``name`` (median over the steady runs), or in the
    scopes ``beneath`` it (forward, recomputed forward and backward, mean over
    the runs)."""
    s = slices(ctx)
    if s is None:
        return None
    if beneath is None:
        return s["ms"].get(name, 0.0)
    return sum(sum(s["by_scope"].get(name, {}).get(b, {}).values()) for b in beneath)


def delta_rule_kernel_ms(ctx) -> float | None:
    """ms per step inside the delta rule's kernel calls (mean over the steady
    runs of the first device), or nothing where the step has no such kernel."""
    if ctx.trace is None or not ctx.trace.devices or slices(ctx) is None:
        return None
    ms = tr.op_time_per_module_ms(ctx.trace, DELTA_RULE_PATTERN, ctx.module_pattern(), ctx.window)
    return sum(ms) / len(ms) if ms and sum(ms) else None
