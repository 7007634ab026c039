"""Model FLOP/s utilization: forward + backward convolution FLOPs of the
published architecture (``harness/flops.py``; nothing recomputed) times the
images per second per chip over the window's log windows before the profiler
starts (host clock), over the chip's published bf16 peak."""


def read(ctx):
    if ctx.peaks is None:
        return None
    return 100.0 * ctx.facts["model_flops_per_s_chip"] / ctx.peaks["flops_bf16"]
