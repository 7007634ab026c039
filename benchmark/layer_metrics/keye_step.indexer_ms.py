"""ms per step of device time in the indexer: its three projections, the
rotation of ``qI`` and ``kI``, and the index scores of every causal pair, each time
they are formed (for the selection, for the indexer's loss, and again when the
layer is recomputed), with the scores' gradient; scope ``attention/indexer``
(``harness/keye_trace.py``)."""

from benchmark.harness import keye_trace


def read(ctx):
    return keye_trace.slice_ms(ctx, "attention", ("indexer",))
