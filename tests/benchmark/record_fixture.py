"""How ``fixture.xplane.pb`` was recorded (on a v5e, through the chip tool):

    python tests/benchmark/record_fixture.py chiprun_out/fixture

Five runs of a small jitted program named ``train_step`` inside the
benchmark's traced window, with the benchmark's own annotations; the file
is a few hundred KB.  Kept so that the fixture can be made again after a
change of JAX's trace format.
"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.harness.runctx import Tracer

    @jax.jit
    def train_step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x, jnp.sum(x)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    x, s = train_step(x, w)
    float(s)
    tracer = Tracer(True, out_dir)
    tracer.start()
    for i in range(5):
        with tracer.annotate("bench.next_batch"):
            time.sleep(0.002)
        x, s = train_step(x, w)
        if i == 2:
            float(s)  # a host sync mid-window: an idle gap on the device
            time.sleep(0.005)
    float(s)
    tracer.stop()
    path = glob.glob(os.path.join(tracer.dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "fixture.xplane.pb"))
    shutil.rmtree(tracer.dir)
    print("recorded", os.path.getsize(os.path.join(out_dir, "fixture.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
