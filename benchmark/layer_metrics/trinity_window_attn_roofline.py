"""The sliding layers' attention's share of its roofline: the least time the chip
could take for the MODEL'S products over the VISIBLE pairs of one step -
``sum_t min(p_t + 1, sliding_window)`` a layer, two products a pair and head
forward (the forward kernel runs once: its output is kept), four backward
(``harness/afmoe_flops.py::attention_cost_per_step``), or for the operands' bytes,
whichever is larger - over the device time of the scope ``attention/window_core``
in a step (``harness/afmoe_trace.py``: the kernels with the scaling and the
transposes around them).  A kernel that masks the window without skipping does
about four times the counted work at T = 16 384 and reads low; the backward
kernels form the scores again (seven products where four are counted); neither
can pass 100%."""

from benchmark.harness import afmoe_flops, afmoe_trace


def read(ctx):
    return afmoe_trace.attention_roofline_pct(ctx, "window_core", "attention_window_pairs_per_step", afmoe_flops.SLIDING)
