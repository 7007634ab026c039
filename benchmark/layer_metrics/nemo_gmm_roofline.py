"""The grouped products' share of their roofline in the Nemotron-H step: the
least time the chip could take for one step's calls (the larger of operations
over the bf16 peak and bytes over peak bytes/s, ``harness/nemotron_flops.py::
gmm_cost_per_step``: TWO products an expert, forward, recomputed forward and two
gradients) over the calls' time in a step (the device trace's ``gmm`` and
``tgmm``), both as means over the SAME steps (``harness/nemotron_trace.py::
gmm_ms_and_rows``: the rows are those of the traced steps themselves).  The
kernel computes whole tiles of 512 rows, a tile on a group boundary twice, and
1920 lanes for a width of 1856; none of that is counted as useful work.  At
768 rows an expert the weights' bytes are no longer small beside the
operations: the reader takes the larger bound, whichever it is."""

from benchmark.harness import flops, nemotron_flops, nemotron_trace


def read(ctx):
    found = nemotron_trace.gmm_ms_and_rows(ctx) if ctx.peaks is not None else None
    if found is None:
        return None
    ms, rows = found
    cost = nemotron_flops.gmm_cost_per_step(ctx.run.config, rows)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
