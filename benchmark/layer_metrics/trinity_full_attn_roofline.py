"""The full layers' attention's share of its roofline: as
``trinity_window_attn_roofline`` over the causal pairs of one document,
``sum_t (p_t + 1)`` a layer, and the device time of the scope
``attention/full_core`` (``harness/afmoe_flops.py::attention_cost_per_step``,
``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_flops, afmoe_trace


def read(ctx):
    return afmoe_trace.attention_roofline_pct(ctx, "full_core", "attention_full_pairs_per_step", afmoe_flops.FULL)
