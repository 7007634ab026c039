"""ms per step of device time in the SLIDING layers' blocked softmax (the call of
``ops/attention.py`` with the window: the scaling, the transposes and the three
kernels over the window's block pairs), all sliding layers together: forward
once (its output and log-sum-exp are kept), dq and dk/dv; scope
``attention/window_core`` (``harness/afmoe_trace.py``)."""

from benchmark.harness import afmoe_trace


def read(ctx):
    return afmoe_trace.slice_ms(ctx, "attention", ("window_core",))
