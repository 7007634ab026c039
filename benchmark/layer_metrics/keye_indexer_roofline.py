"""The indexer's share of its roofline: the least time the chip could take for
the MODEL'S indexer of one step - its three projections and the index scores of
every causal pair of one document, once forward, once in the recomputed forward
and twice for the gradients (``harness/keye_flops.py::indexer_cost_per_step``) -
over the device time of the scope ``attention/indexer`` in a step
(``harness/keye_trace.py``), whatever kernel computes it.  The program forms the
scores more often than that (for the selection and again for the indexer's loss)
and its products are 64 deep on a 128-deep array: neither is counted as useful
work."""

from benchmark.harness import flops, keye_flops, keye_trace


def read(ctx):
    ms = keye_trace.slice_ms(ctx, "attention", ("indexer",)) if ctx.peaks is not None else None
    causal = ctx.facts.get("dsa_causal_pairs_per_step")
    if not ms or not causal:
        return None
    tokens = ctx.run.traffic["per_chip_batch"] * ctx.run.traffic["seq_len"]
    cost = keye_flops.indexer_cost_per_step(ctx.run.config, tokens, causal)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
