"""The Trinity cell's control, by hand: the program computed in the nearest
precision BELOW the one its configuration states, run through ``run.py`` like
any run of the cell, to show that the cell's limits notice (``tolerances`` in
``traffic/lm-swa-moe-train-doc16k-b1.json``).

    python3 -m benchmark.harness.afmoe_control --workload <cell> --seed <n> --seconds 4 --trace 0

Every operand of a matmul with a weight (``models/afmoe.py::_operand``:
activations and weights of attention's five projections, the dense and shared
MLPs, the routed experts' grouped products and the head; NOT the router, which
is float32 by the model's definition, nor attention's own products, whose
operands are activations) is rounded to float8_e4m3fn before it meets, straight
through, BEHIND ``jax.lax.optimization_barrier`` (written as
``x.astype(float8).astype(x.dtype)`` XLA folds it away on the chip: PR 38).
The program has no such option: the control wraps it from here, as
``harness/lm_control.py`` wraps granite.  The run must end ``"correct":
false`` by one of the first step's limits.
"""

from __future__ import annotations

import sys

from benchmark.harness.lm_control import CONTROL_DTYPE


def lower_the_precision() -> None:
    """Patch the program's operand cast."""
    import jax
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.models import afmoe

    stated = afmoe._operand

    def rounded(config, x):
        x = stated(config, x)
        low = jax.lax.optimization_barrier(x.astype(getattr(jnp, CONTROL_DTYPE)))
        return x + jax.lax.stop_gradient(low.astype(x.dtype) - x)

    afmoe._operand = rounded


def main(argv: list[str] | None = None) -> int:
    from benchmark import run

    lower_the_precision()
    print(f"benchmark: CONTROL: matmul operands rounded to {CONTROL_DTYPE}; NOT CORRECT is the expected end", flush=True)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
