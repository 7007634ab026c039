"""ms per step of device time in the FULL layers' blocked softmax (the call of
``ops/attention.py`` without a window: the scaling, the transposes and the three
kernels over the causal block pairs): forward once, dq and dk/dv; scope
``attention/full_core`` (``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "attention", ("full_core",))
