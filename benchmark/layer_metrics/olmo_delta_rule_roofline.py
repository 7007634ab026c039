"""The delta-rule kernels' share of their roofline: the least time the chip could
take for one step's gated delta rules (the larger of operations over the bf16 peak
and bytes over peak bytes/s, ``harness/olmo_flops.py::delta_rule_cost_per_step``: the
minimal chunked algorithm at the program's chunk, every product once forward and
once for each of its two gradients, nothing for the recomputation) over the time
inside the calls ``delta_rule_fwd`` and ``delta_rule_bwd`` in a step
(``harness/olmo_trace.py::delta_rule_kernel_ms``: the kernels' own time, not the
scope's).  The program runs its forward kernel twice a layer and forms the
triangular system's inverse in float32 pieces: neither is counted as useful work,
so the share reads what those choices cost."""

from benchmark.harness import flops, olmo_flops, olmo_trace


def read(ctx):
    ms = olmo_trace.delta_rule_kernel_ms(ctx) if ctx.peaks is not None else None
    chunk = ctx.facts.get("delta_rule_chunk")
    if ms is None or not chunk:
        return None
    tokens = ctx.run.traffic["per_chip_batch"] * ctx.run.traffic["seq_len"]
    cost = olmo_flops.delta_rule_cost_per_step(ctx.run.config, tokens, chunk)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
