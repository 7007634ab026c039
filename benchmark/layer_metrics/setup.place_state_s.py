"""Seconds the host spent placing the state on the mesh before the window: the
program's ``place_state`` phases (twice a run; 0 without a mesh).  By the
rule of ``harness/setup_phases.py``: 0 where the program keeps no phase
record, nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.place_state_s")
