"""The stateless int8 reduce (``comm.compress.bucketed_pmean``: no error
feedback, the plan built from the tree) on the 8-dev CPU mesh, and the
train step that runs it when the state carries no residuals
(``make_train_step(comm=CommConfig(compress="int8"))``, the 2-process pod
worker's "quantized" flavour).

The contract: exact-reduce-then-quantize error bound, a lone small leaf
exact (the undersized-bucket rule), zeros exact, non-finite gradients
surfacing as NaN.  The stateful path's claims (bucketing, error feedback,
overlap, ZeRO composition, checkpoints) live in tests/unit/test_comm.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from batchai_retinanet_horovod_coco_tpu.comm import CommConfig, bucketed_pmean
from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet
from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS
from batchai_retinanet_horovod_coco_tpu.train import create_train_state, make_train_step

N = 8

# The bucket-level exactness floor, in float32 elements.
_MIN_QUANTIZE_ELEMS = CommConfig().min_bucket_bytes // 4


def _run_both(tree):
    """(int8, exact) pmean of a per-device tree on the 8-dev mesh."""
    mesh = make_mesh(N)

    @jax.jit
    @lambda f: shard_map(
        f, mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P(), check_vma=False
    )
    def both(x):
        per_dev = jax.tree.map(lambda a: a[0], x)  # (1, ...) shard → (...)
        return (
            bucketed_pmean(per_dev, DATA_AXIS, N),
            jax.tree.map(lambda a: lax.pmean(a, DATA_AXIS), per_dev),
        )

    return both(tree)


def test_matches_pmean_within_bound():
    rng = np.random.default_rng(0)
    big = rng.normal(0, 0.1, (N, 64, 513)).astype(np.float32)  # odd size, pads
    q, exact = _run_both({"w": jnp.asarray(big)})
    exact_np = np.asarray(exact["w"])
    # Per-element bound: quantization step/2 of the reduced tensor's
    # per-block max; bound with the global max (≥ every block max).
    bound = np.abs(exact_np).max() / 254.0 + 1e-7
    np.testing.assert_allclose(np.asarray(q["w"]), exact_np, atol=float(bound))


def test_small_single_leaf_stays_exact():
    """A lone small leaf forms an undersized bucket -> exact path."""
    rng = np.random.default_rng(1)
    small = rng.normal(0, 1, (N, _MIN_QUANTIZE_ELEMS // 2)).astype(np.float32)
    q, exact = _run_both({"b": jnp.asarray(small)})
    np.testing.assert_array_equal(np.asarray(q["b"]), np.asarray(exact["b"]))


def test_zero_gradients_exact():
    z = jnp.zeros((N, 16, 1024), jnp.float32)
    q, exact = _run_both({"w": z})
    np.testing.assert_array_equal(np.asarray(q["w"]), np.asarray(exact["w"]))


@pytest.mark.slow
def test_train_step_learns_with_quantization():
    model = build_retinanet(
        RetinaNetConfig(
            num_classes=3, backbone="resnet_test", fpn_channels=32,
            head_width=32, head_depth=1, dtype=jnp.float32,
        )
    )
    hw = (64, 64)
    rng = np.random.default_rng(3)
    batch = {
        "images": jnp.asarray(rng.normal(0, 1, (8, *hw, 3)).astype(np.float32)),
        "gt_boxes": jnp.asarray(
            np.tile(np.array([[8.0, 8.0, 40.0, 40.0]], np.float32), (8, 1, 1))
        ),
        "gt_labels": jnp.ones((8, 1), jnp.int32),
        "gt_mask": jnp.ones((8, 1), bool),
    }
    mesh = make_mesh(N)

    def train_n(comm, steps=12):
        state = create_train_state(
            model, optax.adam(1e-3), (1, *hw, 3), jax.random.key(0)
        )
        step = make_train_step(
            model, hw, 3, mesh=mesh, donate_state=False, comm=comm,
        )
        losses = []
        for _ in range(steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses

    q_losses = train_n(CommConfig(compress="int8"))
    e_losses = train_n(None)
    assert q_losses[-1] < q_losses[0], "int8 step failed to learn"
    # Step 1 (identical init, loss computed pre-update) must match exactly;
    # trajectories stay close — int8 on reduced grads is a tiny perturbation.
    np.testing.assert_allclose(q_losses[0], e_losses[0], rtol=1e-6)
    np.testing.assert_allclose(q_losses[-1], e_losses[-1], rtol=0.1)


def test_non_finite_gradients_surface_as_nan():
    """Inf/NaN grads must NOT be laundered into finite int8 garbage — the
    dequantized result goes NaN so the loop's non-finite-loss abort fires
    exactly as it would on the exact-pmean path."""
    rng = np.random.default_rng(2)
    big = rng.normal(0, 0.1, (N, 16, 1024)).astype(np.float32)
    big[3, 5, 100] = np.inf
    q, _ = _run_both({"w": jnp.asarray(big)})
    assert not np.isfinite(np.asarray(q["w"])).all()
