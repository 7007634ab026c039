"""The language-model cell's control, by hand: the program computed in the
nearest precision BELOW the one its configuration states, run through
``run.py`` like any run of the cell, to show that the cell's limits notice
(``tolerances`` in ``traffic/lm-train-pack8k.json``: each lies between what
the program reads and what this control reads).

    python3 -m benchmark.harness.lm_control --workload <cell> --seed <n> --seconds 4 --trace 0

Every operand of a matmul with a weight (``models/granite_hybrid.py::
_operand``: activations and weights of the projections, the MLPs and the
tied head) is rounded to float8_e4m3fn before it meets, straight through
(the backward pass sees the rounded operands and passes the gradient on
unrounded).  The program has no such option: the control wraps it from
here.  The run must end ``"correct": false`` by one of the first step's
limits.
"""

from __future__ import annotations

import sys

CONTROL_DTYPE = "float8_e4m3fn"


def lower_the_precision() -> None:
    """Patch the program's operand cast."""
    import jax
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.models import granite_hybrid

    stated = granite_hybrid._operand

    def rounded(config, x):
        x = stated(config, x)
        return x + jax.lax.stop_gradient(x.astype(getattr(jnp, CONTROL_DTYPE)).astype(x.dtype) - x)

    granite_hybrid._operand = rounded


def main(argv: list[str] | None = None) -> int:
    from benchmark import run

    lower_the_precision()
    print(f"benchmark: CONTROL: matmul operands rounded to {CONTROL_DTYPE}; NOT CORRECT is the expected end", flush=True)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
