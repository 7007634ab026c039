"""ms per step of device time in the gated delta rule (``ops/delta_rule.py``; on a
TPU the kernel pair of ``ops/pallas/delta_rule.py`` beside the sigmoid, the softplus,
the cumulative sums and the layout changes around the kernels): forward, recomputed
forward and backward; scope ``gdn/delta_rule`` (``harness/olmo_trace.py``)."""

from benchmark.harness import olmo_trace


def read(ctx):
    return olmo_trace.slice_ms(ctx, "gdn", ("delta_rule",))
