"""Seconds in the program's ``init_state`` phases before the window
(``train/state.py::create_train_state``; under a caller's own jit, its
trace).  By the rule of ``harness/setup_phases.py``: 0 where the program
keeps no phase record, nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.init_state_s")
