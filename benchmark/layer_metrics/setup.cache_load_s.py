"""Seconds spent fetching executables from the persistent compile cache before
the window: ``retrieval_s`` of the ``xla_compile_or_load`` phases that hit.
By the rule of ``harness/setup_phases.py``: 0 where the program keeps no
phase record, nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.cache_load_s")
