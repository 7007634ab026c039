"""The grouped products' share of their roofline in the Trinity step: the least
time the chip could take for one step's calls (the larger of operations over the
bf16 peak and bytes over peak bytes/s, ``harness/afmoe_flops.py::
gmm_cost_per_step``: gate and up in one product and down, forward, recomputed
forward and two gradients) over the calls' time in a step (the device trace's
``gmm`` and ``tgmm``), both as means over the SAME steps (``harness/afmoe_trace.py::
gmm_ms_and_rows``: the rows are those of the traced steps themselves).  The kernel
computes whole tiles of 512 rows and a tile on a group boundary twice; neither is
counted as useful work."""

from benchmark.harness import afmoe_flops, afmoe_trace, flops


def read(ctx):
    found = afmoe_trace.gmm_ms_and_rows(ctx) if ctx.peaks is not None else None
    if found is None:
        return None
    ms, rows = found
    cost = afmoe_flops.gmm_cost_per_step(ctx.run.config, rows)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
