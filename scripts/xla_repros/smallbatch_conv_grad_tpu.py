#!/usr/bin/env python
"""Standalone repro: 40x conv-backward cliff at small batch on TPU.

A single bf16 3x3 stride-1 NHWC conv at ResNet stage2 geometry
(200x336 spatial, 64 channels — the C3 level of an 800x1344 detection
input) takes ~120-210 ms run-to-run for its gradient at batch 4 but
~5 ms at batch 8 on a v5e chip (jax 0.9.0): a 20-40x non-monotonic
cliff in XLA:TPU's lowering of the backward conv.  Neighbouring
geometries (100x168x128, 50x84x256) scale sanely.

End-to-end effect (round-4 batch-scaling capture): the full RetinaNet
train step is ABSOLUTELY slower at per-chip batch 4 than at batch 8
(146 vs 119 ms/step), and per-image throughput plateaus at ~35 ms/image
for batch <= 4 vs ~15 at batch 8 — so the framework's RUNBOOK recommends
per-chip batch 8 and the linear-scaling LR rule instead of spreading a
small global batch one-image-per-chip.

Requires a real TPU (the cliff is in the TPU lowering; CPU is fine).
Run:  python scripts/xla_repros/smallbatch_conv_grad_tpu.py
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, n: int = 30) -> float:
    compiled = jax.jit(fn).lower(*args).compile()
    out = None
    for _ in range(3):
        out = compiled(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = compiled(*args)
    # Sync inside the timed region: dispatch is asynchronous.
    np.asarray(jax.device_get(jax.tree.leaves(out)[0])).ravel()[0]
    return (time.perf_counter() - t0) / n * 1e3


def main() -> None:
    print(f"jax {jax.__version__}; device {jax.devices()[0].device_kind}")
    rng = np.random.default_rng(0)
    for (H, W, C) in [(200, 336, 64), (100, 168, 128), (50, 84, 256)]:
        w = jnp.asarray(rng.normal(0, 0.05, (3, 3, C, C)), jnp.bfloat16)

        def loss(w, x):
            y = jax.lax.conv_general_dilated(
                x, w, (1, 1), ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            return jnp.sum(y.astype(jnp.float32))

        g = jax.grad(loss)
        times = {}
        for b in (4, 8):
            x = jnp.asarray(rng.normal(0, 1, (b, H, W, C)), jnp.bfloat16)
            times[b] = timeit(g, w, x)
        flag = "  <== CLIFF" if times[4] > 3 * times[8] else ""
        print(
            f"conv {H}x{W}x{C}: grad b4 {times[4]:7.2f} ms vs "
            f"b8 {times[8]:6.2f} ms{flag}"
        )


if __name__ == "__main__":
    sys.exit(main())
