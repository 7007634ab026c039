#!/usr/bin/env python
"""HLO collective audit: verify the sharded step's communication schedule.

PARITY.md's scaling argument (BASELINE.json ≥90% efficiency at 8→256
chips) rests on one premise: the DP step's gradient synchronization
compiles to a SMALL number of fused all-reduce ops moving ≈152 MB of
f32 gradients (37.97M flagship params × 4 B), which at ~100 GB/s ICI
ring bandwidth costs ≈3 ms against a 135 ms step.  This script makes
that premise checkable: it compiles the real flagship-width train step
over an ``--devices N`` virtual CPU mesh, parses the OPTIMIZED HLO, and
reports every collective with its result-shape payload.

Measured (jax 0.9.0, CPU backend, f32 flagship width, SGD+momentum):
the whole module contains exactly ONE variadic all-reduce — XLA's
combiner fuses the entire gradient tree AND the pmean'd metrics/num_pos
scalars into a single add-reduction collective — with payload
152.0 MB, independent of N (verified n=8 and n=32; pinned by
tests/distributed/test_scale_evidence.py).  The ZeRO flavor
(``--zero``) replaces it with reduce-scatter(grads)/all-gather(params)
whose payloads shrink as 1/N per shard.

Run:
    python scripts/audit_collectives.py --devices 32 --json
    python scripts/audit_collectives.py --devices 8 --zero
"""

import argparse
import json
import os
import re
import sys

# Base collective op names; the parser also matches each one's async
# "-start" form (emitted on backends/flags with async collectives) and
# folds it into the base name so a schedule audits uniformly.  Async
# "-start" results are (operand, result, ...) tuples; the operand half is
# an aliased copy of the input, so only the RESULT elements are counted
# (``_async_result_bytes``) — payloads match the sync form exactly, and
# the matching "-done" halves are never separately counted.
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of every typed array in an HLO result-shape string
    (handles tuples: '(f32[3,3,64,64]{3,2,1,0}, f32[64]{0}, ...)')."""
    total = 0
    for m in re.finditer(r"(\w+)\[([0-9,]*)\]", shape_text):
        dt = _DTYPE_BYTES.get(m.group(1))
        if dt is None:
            continue
        n = 1
        for d in filter(None, m.group(2).split(",")):
            n *= int(d)
        total += n * dt
    return total


def _split_top_level(shape_text: str) -> list[str]:
    """Top-level elements of a tuple shape string: ``(f32[3,3]{1,0},
    (f32[4]{0}, f32[4]{0}))`` -> ['f32[3,3]{1,0}', '(f32[4]{0}, f32[4]{0})'].
    Returns [] when the text is not a tuple."""
    s = shape_text.strip()
    if not s.startswith("("):
        return []
    depth = 0
    elems, start = [], 1
    for i, c in enumerate(s):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                elems.append(s[start:i].strip())
                break
        elif c == "," and depth == 1:
            elems.append(s[start:i].strip())
            start = i + 1
    return [e for e in elems if e]


def _async_result_bytes(shape_text: str) -> int:
    """Payload of an async ``op-start`` result WITHOUT the operand alias:
    start ops return ``(operand, result, ...context)`` tuples, so counting
    the whole tuple over-counts ~2x vs. the sync form.  Drop the first
    element (the aliased input) and count the rest; a non-tuple start
    result (bufferized forms) is counted whole."""
    elems = _split_top_level(shape_text)
    if len(elems) < 2:
        return _shape_bytes(shape_text)
    return sum(_shape_bytes(e) for e in elems[1:])


def audit_hlo_text(txt: str) -> dict:
    """Parse optimized HLO, return {op: {count, payload_bytes}} with
    async ``op-start`` instructions folded into their base op name:
    payload from the start's RESULT elements only (operand-alias halves
    dropped), and the matching ``op-done`` instructions never separately
    counted."""
    out: dict[str, dict[str, int]] = {}
    # `%name = SHAPE op-name(operands...)`; SHAPE may be a long tuple, so
    # split the line at the op-name rather than regexing the whole shape.
    for line in txt.splitlines():
        for op in _COLLECTIVES:
            for marker, is_start in ((f" {op}-start(", True),
                                     (f" {op}(", False)):
                if marker in line and "=" in line.split(marker)[0]:
                    lhs = line.split(marker)[0].split("=", 1)[1]
                    rec = out.setdefault(op, {"count": 0, "payload_bytes": 0})
                    rec["count"] += 1
                    rec["payload_bytes"] += (
                        _async_result_bytes(lhs) if is_start
                        else _shape_bytes(lhs)
                    )
                    break
            else:
                continue
            break
    return out


def compile_and_audit(
    n_devices: int, reduced: bool, zero: bool
) -> dict:
    # Must run before any other jax use in this process: the platform and
    # the virtual device count are read when the backend initializes.
    # The audit reads the OPTIMIZED HLO, so it always compiles fresh.
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    assert jax.device_count() == n_devices, (
        f"virtual CPU mesh came up with {jax.device_count()} devices, "
        f"wanted {n_devices}"
    )

    import jax.numpy as jnp
    import numpy as np
    import optax

    from batchai_retinanet_horovod_coco_tpu.models import (
        RetinaNetConfig,
        build_retinanet,
    )
    from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh
    from batchai_retinanet_horovod_coco_tpu.train import (
        create_train_state,
        make_train_step,
    )

    width = {"fpn_channels": 64, "head_width": 64} if reduced else {}
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=80, backbone="resnet50", dtype=jnp.float32, **width
        )
    )
    hw = (64, 64)  # fully-conv: the GRADIENT payload is width-set, not hw-set
    state = create_train_state(
        model, optax.sgd(1e-2, momentum=0.9), (1, *hw, 3), jax.random.key(0)
    )
    num_params = int(sum(x.size for x in jax.tree.leaves(state.params)))
    mesh = make_mesh(n_devices)
    step = make_train_step(
        model, hw, 80, mesh=mesh, donate_state=False,
        shard_weight_update=zero,
    )
    if zero:
        from batchai_retinanet_horovod_coco_tpu.parallel import (
            init_sharded_opt_state,
        )

        state = state.replace(
            opt_state=init_sharded_opt_state(state.tx, state.params, mesh)
        )
    batch = {
        "images": jnp.zeros((n_devices, *hw, 3), jnp.float32),
        "gt_boxes": jnp.tile(
            jnp.asarray([[8.0, 8.0, 40.0, 40.0]]), (n_devices, 1, 1)
        ),
        "gt_labels": jnp.zeros((n_devices, 1), jnp.int32),
        "gt_mask": jnp.ones((n_devices, 1), bool),
    }
    compiled = step.lower(state, batch).compile()
    collectives = audit_hlo_text(compiled.as_text())
    return {
        "devices": n_devices,
        "flavor": "zero" if zero else "dp",
        "width": "reduced" if reduced else "flagship",
        "num_params": num_params,
        "grad_bytes_f32": num_params * 4,
        "collectives": collectives,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="audit the reduced-width model (default: flagship)")
    ap.add_argument("--zero", action="store_true",
                    help="audit the ZeRO (weight-update-sharded) flavor")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    )
    result = compile_and_audit(args.devices, args.reduced, args.zero)
    if args.json:
        print(json.dumps(result))
        return
    print(
        f"{result['flavor']} step, {result['width']} width, "
        f"{result['devices']} devices: {result['num_params'] / 1e6:.2f}M "
        f"params -> {result['grad_bytes_f32'] / 1e6:.1f} MB f32 grads"
    )
    if not result["collectives"]:
        print("  NO collectives found (single-device module?)")
    for op, rec in sorted(result["collectives"].items()):
        print(
            f"  {op:20s} x{rec['count']:3d}  payload "
            f"{rec['payload_bytes'] / 1e6:8.1f} MB"
        )


if __name__ == "__main__":
    main()
