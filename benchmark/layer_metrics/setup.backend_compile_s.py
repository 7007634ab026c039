"""Seconds in the compiler before the window: the duration of the
``xla_compile_or_load`` phases that did not hit (0 where the machine's cache
holds the cell).  By the rule of ``harness/setup_phases.py``: 0 where the
program keeps no phase record, nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.backend_compile_s")
