"""ms per step of device time in the attention layers (their norm, q, k, v, o with
the per-head norms and the rotation, the indexer, the selection, the selected
attention and the indexer's loss): forward, recomputed forward and backward;
scope ``attention`` (``harness/keye_trace.py``)."""

from benchmark.harness import keye_trace


def read(ctx):
    return keye_trace.slice_ms(ctx, "attention")
