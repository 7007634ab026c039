"""The grouped-matmul kernel's share of its roofline: the least time the chip
could take for the kernel's calls of one step (the larger of operations over
the bf16 peak and bytes over peak bytes/s, ``harness/moe_lm_flops.py::
gmm_cost_per_step``) over the kernel's time in a step (the device trace's
``gmm`` and ``tgmm`` calls), both as means over the SAME steps: a product's
work follows the rows routed, routing moves as the model trains, so the rows
are those of the traced steady steps themselves (``harness/moe_lm_trace.py::
gmm_ms_and_rows``).  At these widths the operations bound applies (1536 rows
and more an expert against 17 MB of weights).  The kernel computes whole
tiles of 512 rows and a tile on a group boundary twice; neither is counted as
useful work."""

from benchmark.harness import flops, moe_lm_flops, moe_lm_trace


def read(ctx):
    found = moe_lm_trace.gmm_ms_and_rows(ctx) if ctx.peaks is not None else None
    if found is None:
        return None
    ms, rows = found
    cost = moe_lm_flops.gmm_cost_per_step(ctx.run.config, rows)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
