"""The Olmo-Hybrid cell's two controls, by hand: the program computed in the
nearest precision BELOW the one its configuration states, run through ``run.py``
like any run of the cell, to show that the cell's limits notice (``tolerances``
in ``traffic/lm-linear-train-pack8k-fixed.json``).

    python3 -m benchmark.harness.olmo_control --control operands --workload <cell> --seed <n> --seconds 4 --trace 0
    python3 -m benchmark.harness.olmo_control --control state --workload <cell> --seed <n> --seconds 4 --trace 0

``operands``: every operand of a matmul with a weight (``models/olmo_hybrid.py::
_operand``: activations and weights of the mixers' projections, the MLPs and the
head) is rounded to float8_e4m3fn before it meets, straight through.  ``state``:
the delta rule's keys and values (``models/olmo_hybrid.py::_state_operand``) and
the carried state wherever the kernels multiply with it (``ops/pallas/
delta_rule.py::_state_operand``) are rounded alike, and nothing else.  Inside a
kernel the rounding is done in float32 arithmetic on the bits (three bits of
mantissa kept, to nearest even; the chip's vector unit converts no 8-bit float):
float8_e4m3fn's values wherever the number is in its normal range, which a
state of seeded weights is not always - below 2^-6 the control keeps three bits
where the format would keep fewer, so it lowers the precision by no more than the
format would.  The program has neither option: the control wraps it from here, as
``harness/lm_control.py`` wraps granite.  Either run must end ``"correct": false``
by one of the first step's limits.
"""

from __future__ import annotations

import argparse
import sys

from benchmark.harness.lm_control import CONTROL_DTYPE

CONTROLS = ("operands", "state")


def three_mantissa_bits(x):
    """float32 ``x`` rounded to three bits of mantissa, to nearest even, by its bits."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    dropped = 20  # of float32's 23
    rounded = bits + jnp.uint32((1 << (dropped - 1)) - 1) + ((bits >> dropped) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(rounded & jnp.uint32(~((1 << dropped) - 1) & 0xFFFFFFFF), jnp.float32)


def lower_the_precision(control: str) -> None:
    """Patch the program's operand casts."""
    import jax
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.models import olmo_hybrid
    from batchai_retinanet_horovod_coco_tpu.ops.pallas import delta_rule as kernels

    def through_the_format(stated):
        def rounded(config, x):
            x = stated(config, x)
            # behind a barrier: a compiler that may keep more precision than it is asked for (XLA's default)
            # otherwise folds the way down and back up into nothing
            low = jax.lax.optimization_barrier(x.astype(getattr(jnp, CONTROL_DTYPE)))
            return x + jax.lax.stop_gradient(low.astype(x.dtype) - x)
        return rounded

    if control == "operands":
        olmo_hybrid._operand = through_the_format(olmo_hybrid._operand)
        return
    olmo_hybrid._state_operand = through_the_format(olmo_hybrid._state_operand)
    kernels._state_operand = lambda s, dtype: three_mantissa_bits(s.astype(jnp.float32)).astype(dtype)


def main(argv: list[str] | None = None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--control", choices=CONTROLS, required=True)
    args, rest = ap.parse_known_args(argv)
    lower_the_precision(args.control)
    print(f"benchmark: CONTROL {args.control}: rounded to {CONTROL_DTYPE}; NOT CORRECT is the expected end", flush=True)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
