"""ms per step the train loop waited for its next batch: the loop's own
``data_wait_ms`` (mean per step of each log window), median over the
window's log windows."""

from benchmark.harness.trace_reduce import median


def read(ctx):
    return median(ctx.facts["data_wait_ms"])
