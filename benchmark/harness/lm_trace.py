"""The language-model step's device time by named scope, for the
``lm_step.*`` readers: ``program_trace.slices`` (the traced run's device
operations joined by instruction name with a scope table, written to
``<out_dir>/slices.json`` and printed as ``benchmark: slices``) given the
table of the step the loop ran (``train/loop.py::compiled_step`` ->
``train/step.py::scope_table``).  ``program_trace`` builds its own table only
for a detection step; against a program that has no language-model step or
scopes every function here returns nothing and raises nothing."""

from __future__ import annotations

from benchmark.harness import program_trace

EXPECTED = {"mamba", "attention", "mlp", "lm_head", "optimizer"}


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


def slice_ms(ctx, name: str, beneath: str | None = None) -> float | None:
    """ms per step in slice ``name`` (median over the steady runs), or in
    the scope ``beneath`` it (forward and backward, mean over the runs)."""
    s = slices(ctx)
    if s is None:
        return None
    if beneath is None:
        return s["ms"].get(name, 0.0)
    return sum(s["by_scope"].get(name, {}).get(beneath, {}).values())
