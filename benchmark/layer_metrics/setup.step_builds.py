"""Times a train step was built before the window: the program's
``compile_train_step`` phases (2 today: the warm call's and the measured
call's).  By the rule of ``harness/setup_phases.py``: 0 where the program
keeps no phase record, nothing where its record is broken."""

from benchmark.harness import setup_phases


def read(ctx):
    return setup_phases.value(ctx, "setup.step_builds")
