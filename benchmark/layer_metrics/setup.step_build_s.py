"""Seconds building the train step cost the run before the window: the summed
duration of the ``compile_train_step`` phases, children (trace, lowering,
load or compile, first call) included.  By the rule of
``harness/setup_phases.py``: 0 where the program keeps no phase record,
nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.step_build_s")
