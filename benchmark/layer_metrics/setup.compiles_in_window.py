"""Programs compiled or loaded inside the measured window (must be 0):
the benchmark's compile counter, a copy of ``chip_smoke.py``'s."""


def read(ctx):
    return ctx.facts["compiles_in_window"]
