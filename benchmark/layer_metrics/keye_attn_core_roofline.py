"""The main attention's share of its roofline: the least time the chip could
take for the MODEL'S attention of one step - scores and values over the SELECTED
pairs only, ``sum_t min(p_t + 1, topk)`` a layer, forward, recomputed forward and
two gradients (``harness/keye_flops.py::attention_core_cost_per_step``) - over the
device time of the scope ``attention/attention_core`` in a step
(``harness/keye_trace.py``), whatever kernel or mask computes it.  A version that
forms every causal block under a dense mask does about four times the counted
work at T = 16 384 and reads low; one that skips what was not selected reads
higher; neither can pass 100%."""

from benchmark.harness import flops, keye_flops, keye_trace


def read(ctx):
    ms = keye_trace.slice_ms(ctx, "attention", ("attention_core",)) if ctx.peaks is not None else None
    selected = ctx.facts.get("dsa_selected_pairs_per_step")
    if not ms or not selected:
        return None
    tokens = ctx.run.traffic["per_chip_batch"] * ctx.run.traffic["seq_len"]
    cost = keye_flops.attention_core_cost_per_step(ctx.run.config, tokens, selected)
    return 100.0 * flops.roofline_share(cost, ms / 1e3, ctx.peaks)["share"]
