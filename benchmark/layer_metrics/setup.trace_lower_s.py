"""Seconds the host spent tracing and lowering before the window: the self time
of every ``jit_trace`` and ``jit_lower`` phase the program filed (the step,
twice today, and every smaller program), a trace begun inside another
counted once.  By the rule of ``harness/setup_phases.py``: 0 where the
program keeps no phase record, nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.trace_lower_s")
