"""The Keye-VL-2.0 cell's two controls, by hand: the program computed in the
nearest precision BELOW the one its configuration states, run through ``run.py``
like any run of the cell, to show that the cell's limits notice (``tolerances``
in ``traffic/lm-dsa-moe-train-doc16k-b1.json``).

    python3 -m benchmark.harness.keye_control --control operands --workload <cell> --seed <n> --seconds 4 --trace 0
    python3 -m benchmark.harness.keye_control --control scores --workload <cell> --seed <n> --seconds 4 --trace 0

``operands``: every operand of a matmul with a weight outside the indexer
(``models/keye_vl2.py::_operand``: activations and weights of the attention's
four projections, the routed experts' grouped products and the head; NOT the
router, which is float32 by the model's definition, and NOT the indexer, which
the other control lowers) is rounded to float8_e4m3fn before it meets, straight
through.  ``scores``: the two operands of the index scores' products
(``_score_operand``: the rotated ``qI`` and ``kI``) are rounded alike, and
nothing else: the selection moves, the mathematics on a given selection does
not.  The program has neither option: the control wraps it from here, as
``harness/lm_control.py`` wraps granite.  Either run must end ``"correct":
false`` by one of the first step's limits.
"""

from __future__ import annotations

import argparse
import sys

from benchmark.harness.lm_control import CONTROL_DTYPE

PATCHED = {"operands": "_operand", "scores": "_score_operand"}


def lower_the_precision(control: str) -> None:
    """Patch one of the program's operand casts."""
    import jax
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.models import keye_vl2

    stated = getattr(keye_vl2, PATCHED[control])

    def rounded(config, x):
        x = stated(config, x)
        # behind a barrier: a compiler that may keep more precision than it is asked for (XLA's default) otherwise
        # folds the way down and back up into nothing, and the control computes what the program computes
        low = jax.lax.optimization_barrier(x.astype(getattr(jnp, CONTROL_DTYPE)))
        return x + jax.lax.stop_gradient(low.astype(x.dtype) - x)

    setattr(keye_vl2, PATCHED[control], rounded)


def main(argv: list[str] | None = None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--control", choices=sorted(PATCHED), required=True)
    args, rest = ap.parse_known_args(argv)
    lower_the_precision(args.control)
    print(f"benchmark: CONTROL {args.control}: {PATCHED[args.control]} rounded to {CONTROL_DTYPE}; "
          "NOT CORRECT is the expected end", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
