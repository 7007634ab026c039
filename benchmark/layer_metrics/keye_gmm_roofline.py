"""The grouped products' share of their roofline in the Keye-VL-2.0 step: the
least time the chip could take for one step's calls (the larger of operations
over the bf16 peak and bytes over peak bytes/s, ``harness/keye_flops.py::
gmm_cost_per_step``: gate and up in one product and down, forward, recomputed
forward and two gradients) over the calls' time in a step (the device trace's
``gmm`` and ``tgmm``), both as means over the SAME steps (``harness/keye_trace.py::
gmm_ms_and_rows``: the rows are those of the traced steps themselves).  The kernel
computes whole tiles of 512 rows and a tile on a group boundary twice; neither is
counted as useful work.  At 1024 rows an expert the weights' bytes are no longer
small beside the operations: the reader takes the larger bound, whichever it is."""

from benchmark.harness import flops, keye_flops, keye_trace


def read(ctx):
    found = keye_trace.gmm_ms_and_rows(ctx) if ctx.peaks is not None else None
    if found is None:
        return None
    ms, rows = found
    cost = keye_flops.gmm_cost_per_step(ctx.run.config, rows)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
