"""Golden-step determinism harness (SURVEY.md §4.4).

A fixed-PRNG, fixed-data 5-step loss trajectory recorded in-repo: any
refactor that changes numerics (op reordering, dtype drift, matcher changes)
shows up as a diff here before it shows up as silent mAP loss.  Loss also
must strictly decrease — the 'loss goes down' smoke the reference relied on,
made deterministic.

Goldens recorded on the 8-device virtual CPU mesh, f32, jax 0.9.0, and
reproduced on the installed jax 0.9.0 to ≤ 2e-6 relative.
Regenerate (only for an INTENDED numerics change or a runtime move) with:
  python -m tests.integration.test_golden
"""

if __name__ == "__main__":
    # Regeneration must run on the same backend the pytest assertion uses
    # (conftest.py forces CPU only under pytest; bare python would pick the
    # host's default backend and could record goldens from an accelerator).
    import os

    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet
from batchai_retinanet_horovod_coco_tpu.train import create_train_state, make_train_step

HW = (64, 64)
GOLDEN_LOSSES = (
    5.7837281227,
    5.7642784119,
    5.7254600525,
    5.6187024117,
    5.1890058517,
)


def run_trajectory() -> list[float]:
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=3, backbone="resnet_test", fpn_channels=16,
            head_width=16, head_depth=1, dtype=jnp.float32,
        )
    )
    state = create_train_state(
        model, optax.sgd(1e-2, momentum=0.9), (1, *HW, 3), jax.random.key(42)
    )
    step = make_train_step(model, HW, 3)
    rng = np.random.default_rng(42)
    batch = {
        "images": jnp.asarray(rng.normal(0, 1, (4, *HW, 3)).astype(np.float32)),
        "gt_boxes": jnp.asarray(
            np.tile(np.array([[10.0, 10.0, 50.0, 50.0]], np.float32), (4, 1, 1))
        ),
        "gt_labels": jnp.ones((4, 1), jnp.int32),
        "gt_mask": jnp.ones((4, 1), bool),
    }
    losses = []
    for _ in range(len(GOLDEN_LOSSES)):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses


def test_golden_loss_trajectory():
    losses = run_trajectory()
    # rel 1e-5: loose enough for XLA version-to-version scheduling noise,
    # tight enough to catch any real numerics change.
    np.testing.assert_allclose(losses, GOLDEN_LOSSES, rtol=1e-5)
    assert all(b < a for a, b in zip(losses, losses[1:])), "loss must decrease"


if __name__ == "__main__":
    print("recorded:", [f"{l:.10f}" for l in run_trajectory()])
