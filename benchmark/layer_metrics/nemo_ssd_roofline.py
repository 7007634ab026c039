"""The grouped scan kernels' share of their roofline: the least time the chip
could take for one step's scans (the larger of operations over the bf16 peak
and bytes over peak bytes/s, ``harness/nemotron_flops.py::ssd_cost_per_step``:
the minimal chunked algorithm at the program's chunk, forward, recomputed
forward and backward of every Mamba-2 layer) over the time inside the calls
``ssd_scan_fwd`` and ``ssd_scan_bwd`` in a step (``harness/nemotron_trace.py::
ssd_ms``).  The operations bound applies; decays and masks (VPU work) and what
the backward kernel forms again are not counted as useful work."""

from benchmark.harness import flops, nemotron_flops, nemotron_trace


def read(ctx):
    ms = nemotron_trace.ssd_ms(ctx) if ctx.peaks is not None else None
    chunk = ctx.facts.get("ssd_chunk")
    if ms is None or not chunk:
        return None
    tokens = ctx.run.traffic["per_chip_batch"] * ctx.run.traffic["seq_len"]
    cost = nemotron_flops.ssd_cost_per_step(ctx.run.config, tokens, chunk)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
