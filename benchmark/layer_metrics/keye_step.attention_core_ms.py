"""ms per step of device time in the main attention over the selected keys: the
blocked softmax under the selection's mask, forward, recomputed forward, dq and
dk/dv; scope ``attention/attention_core`` (``harness/keye_trace.py``)."""

from benchmark.harness import keye_trace


def read(ctx):
    return keye_trace.slice_ms(ctx, "attention", ("attention_core",))
