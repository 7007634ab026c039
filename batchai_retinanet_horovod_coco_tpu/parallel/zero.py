"""ZeRO-style weight-update sharding over the ``data`` mesh axis.

The reference replicates optimizer state on every GPU and applies the same
update N times (Horovod's model, SURVEY.md §2.4).  This optional mode shards
the *weight update* instead — the cross-replica weight-update sharding of
PAPERS.md "Automatic Cross-Replica Sharding of Weight Update" and the ZeRO
optimizer-state partitioning idea:

- gradients leave the backward pass via ``psum_scatter`` (reduce-scatter):
  each device receives the 1/N shard of the summed gradient it owns —
  half the collective bytes of the plain ``pmean`` all-reduce;
- each device stores ONLY its 1/N shard of the optimizer state (momentum /
  Adam moments: the dominant state memory) and updates its 1/N of the
  parameters;
- updated parameter shards return to full replication via a tiled
  ``all_gather`` (reduce_scatter + all_gather == all_reduce, so the total
  collective traffic matches the baseline while state memory and update
  compute drop by N).

Storage layout: every parameter leaf is flattened, zero-padded to a multiple
of N, and its optimizer-state counterparts live as global ``(N * chunk,)``
arrays sharded on the leading axis.  Scalar state (schedule counts, plateau
controllers) stays replicated.

The pytree STRUCTURE of a sharded opt_state is identical to the replicated
one (``tx.init`` over a params-like tree of shards), only the leaf shapes
differ — and because the padding is zeros, converting between world sizes
(or to/from the replicated layout) is pure shape surgery:
``reshard_flat_leaf`` below truncates-or-zero-pads the flat representation
to the target layout, refusing loudly if the truncated tail carries data.
That is what makes checkpoints world-size-elastic (ISSUE 11,
utils/checkpoint.py): a ZeRO checkpoint saved at world N restores at world
M ≠ N — including M = 1, the replicated single-host recovery of a pod
snapshot — which the reference could not do (Horovod checkpoints assumed
the same world size for optimizer slots), and which the weight-update
sharding paper (PAPERS.md) treats as the resharding problem.

Gradient clipping: ``optax.clip_by_global_norm`` inside the chain would see
only the local shard and compute a wrong norm, so the chain is built without
it (train/optim.py ``include_clip=False``) and the step applies the same
``scale = clip / max(norm, clip)`` rule from the psum of per-shard square
sums — bitwise-equivalent semantics, global by construction.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS


def _chunk(size: int, n: int) -> int:
    return -(-size // n)


def _pad_flat(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Flatten and zero-pad to ``n * chunk`` elements."""
    flat = x.reshape(-1)
    pad = n * _chunk(flat.size, n) - flat.size
    return jnp.pad(flat, (0, pad)) if pad else flat


def _local_shard(x: jnp.ndarray, n: int, index: jnp.ndarray) -> jnp.ndarray:
    """This device's ``(chunk,)`` slice of a padded-flat parameter."""
    flat = _pad_flat(x, n)
    chunk = flat.size // n
    return lax.dynamic_slice(flat, (index * chunk,), (chunk,))


def _unshard(shard: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """All-gather shards back into the original leaf shape."""
    full = lax.all_gather(shard, DATA_AXIS, tiled=True)
    return full[: like.size].reshape(like.shape)


def shard_template(params: Any, n: int) -> Any:
    """Per-device parameter-shard ShapeDtypeStructs (tx.init template)."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((_chunk(p.size, n),), p.dtype), params
    )


def opt_state_partition_specs(opt_state: Any) -> Any:
    """PartitionSpec tree for a sharded opt_state (THE storage-format rule).

    Rule: state leaves derived from parameters are 1-D ``(chunk,)`` per
    device → sharded on the leading axis; scalar leaves (counts, plateau
    controllers) are replicated.  Every optax transform used by
    train/optim.py fits this shape dichotomy by construction.  This is the
    single owner of the rule — the train step's shard_map specs and the
    loop's post-restore placement both derive from here.
    """
    return jax.tree.map(
        lambda l: P(DATA_AXIS) if getattr(l, "ndim", 0) >= 1 else P(),
        opt_state,
    )


def opt_state_specs(tx: optax.GradientTransformation, params: Any, n: int) -> Any:
    """PartitionSpec tree for the sharded opt_state of ``tx`` over ``params``."""
    return opt_state_partition_specs(
        jax.eval_shape(tx.init, shard_template(params, n))
    )


def clip_by_global_norm_sharded(
    max_norm: float,
    axis_name: str = DATA_AXIS,
    use_precomputed: bool = True,
) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` for updates living as 1/N shards.

    The in-chain optax clip would compute the norm of the LOCAL shard only;
    this transform psums the per-shard square sums over ``axis_name`` (the
    shards partition the full gradient exactly; padding contributes zeros),
    so the clip decision is global.  Because it sits INSIDE the optax chain,
    ``optax.multi_transform`` masking (--freeze-backbone) applies to it
    exactly as to the replicated clip: frozen leaves never enter the norm.
    Must run inside ``shard_map`` (uses a named-axis collective).
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None, *, grad_norm=None, **extra):
        del params, extra
        if grad_norm is None or not use_precomputed:
            # Self-computed: psum the per-shard square sums (the shards
            # partition the full gradient exactly; padding is zeros).
            # ``use_precomputed=False`` FORCES this — a freeze-masked
            # chain sees only its subtree, whose norm differs from the
            # step's full-tree value (train/optim.py).
            sq = sum(
                jnp.sum(jnp.square(g)) for g in jax.tree.leaves(updates)
            )
            norm = jnp.sqrt(lax.psum(sq, axis_name))
        else:
            # sharded_update already psum-ed this exact norm for its
            # grad_norm metric (ISSUE 10: the pre-clip norm is computed
            # once and shared, never recomputed).
            norm = grad_norm
        scale = max_norm / jnp.maximum(norm, max_norm)
        return jax.tree.map(lambda g: g * scale, updates), state

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def reshard_flat_leaf(saved, shape, dtype, path: str = ""):
    """Re-lay one optimizer-state leaf saved in one ZeRO/replicated layout
    into another (host-side numpy; the checkpoint restore path).

    The storage-format rule (``opt_state_partition_specs``) means a leaf is
    either its logical parameter shape (replicated layout) or a flat
    zero-padded ``(N * chunk,)`` array (world-N sharded layout), and the
    padded flat form CONTAINS the logical content as a prefix with zeros
    after it.  So any layout→layout conversion is: flatten, truncate or
    zero-pad to the target element count, reshape — valid iff every
    truncated element is zero (anything else means the checkpoint does not
    actually hold this parameter's state: wrong model, wrong optimizer, or
    corruption — refuse loudly rather than silently drop data).
    """
    import numpy as np

    saved = np.asarray(saved)
    shape = tuple(int(d) for d in shape)
    if saved.dtype != np.dtype(dtype):
        raise ValueError(
            f"checkpoint leaf {path or '<leaf>'}: dtype "
            f"{saved.dtype} != expected {np.dtype(dtype)}"
        )
    if saved.shape == shape:
        return saved
    if saved.ndim != 1 and len(shape) != 1:
        # Neither side is a flat ZeRO layout — this is a genuine model/
        # optimizer mismatch, not a resharding problem.
        raise ValueError(
            f"checkpoint leaf {path or '<leaf>'}: shape {saved.shape} != "
            f"expected {shape} and neither is a flat ZeRO layout"
        )
    flat = saved.reshape(-1)
    target = 1
    for d in shape:
        target *= d
    if flat.size > target:
        if np.count_nonzero(flat[target:]):
            raise ValueError(
                f"checkpoint leaf {path or '<leaf>'}: truncating "
                f"{flat.size} -> {target} elements would drop non-zero "
                "state (not ZeRO padding) — the checkpoint does not match "
                "this model/optimizer"
            )
        flat = flat[:target]
    elif flat.size < target:
        flat = np.pad(flat, (0, target - flat.size))
    return np.ascontiguousarray(flat.reshape(shape))


def init_sharded_opt_state(
    tx: optax.GradientTransformation, params: Any, mesh: Mesh
) -> Any:
    """Build the global sharded opt_state for ``params`` on ``mesh``.

    Each device initializes the transform on its own parameter shard; the
    result is the global pytree whose sharded leaves are ``(N * chunk,)``
    arrays laid out along the ``data`` axis.
    """
    n = mesh.size
    specs = opt_state_specs(tx, params, n)

    @partial(
        shard_map, mesh=mesh, in_specs=(P(),), out_specs=specs, check_vma=False
    )
    def init(p):
        index = lax.axis_index(DATA_AXIS)
        shards = jax.tree.map(lambda x: _local_shard(x, n, index), p)
        return tx.init(shards)

    return jax.jit(init)(params)


def sharded_update(
    tx: optax.GradientTransformation,
    grads: Any,
    opt_state: Any,
    params: Any,
    *,
    n: int,
    loss_value: jnp.ndarray | None = None,
    gather_updates=None,
) -> tuple[Any, Any, dict[str, jnp.ndarray]]:
    """One weight update on this device's shard; call INSIDE shard_map.

    ``grads`` are the local per-device gradients (pre-allreduce); the
    reduce-scatter happens here.  Gradient clipping is ``tx``'s concern:
    build the chain with ``clip_by_global_norm_sharded`` (train/optim.py
    ``shard_clip_axis``) so the norm is global across shards.  Returns
    (new_params FULL via all_gather, new_opt_state local shards,
    info dict with the pre-clip ``grad_norm`` — SURVEY.md §5.5 metric).

    ``gather_updates(updates, params) -> new_params`` (optional, ISSUE
    13): replaces the f32 param all-gather with a caller-owned
    collective over the optax UPDATE shards — the comm subsystem's
    compressed update gather (``comm/compress.zero_gather_updates``),
    which is what makes ZeRO + compression composable (gathering the
    gradient-like update with error feedback instead of quantizing the
    params themselves).  The gradient reduce-scatter, the sharded
    optimizer update, and the global clip norm are UNCHANGED either way.
    """
    # Scopes as in train/step.py::STEP_SCOPES: the collectives are
    # grad_allreduce, the shard-local arithmetic between them optimizer.
    index = lax.axis_index(DATA_AXIS)
    with jax.named_scope("grad_allreduce"):
        gshards = jax.tree.map(
            lambda g: lax.psum_scatter(_pad_flat(g, n), DATA_AXIS, tiled=True) / n,
            grads,
        )
        # The shards partition the mean gradient exactly (padding is zeros), so
        # the global norm is the psum of per-shard square sums.
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(gshards))
        info = {"grad_norm": jnp.sqrt(lax.psum(sq, DATA_AXIS))}
    with jax.named_scope("optimizer"):
        pshards = jax.tree.map(lambda p: _local_shard(p, n, index), params)
        if isinstance(tx, optax.GradientTransformationExtraArgs):
            # Forward the already-psum-ed pre-clip norm so the in-chain
            # sharded clip reuses it instead of a second psum; value= feeds
            # reduce_on_plateau when the schedule carries one.
            extra = {"grad_norm": info["grad_norm"]}
            if loss_value is not None:
                extra["value"] = loss_value
            updates, new_opt_state = tx.update(
                gshards, opt_state, pshards, **extra
            )
        else:
            updates, new_opt_state = tx.update(gshards, opt_state, pshards)
    if gather_updates is not None:
        # Compressed path: every device applies the identical
        # dequantized full update to its replicated params, so the
        # params stay bitwise replicated without an f32 gather.
        return gather_updates(updates, params), new_opt_state, info
    with jax.named_scope("optimizer"):
        new_pshards = optax.apply_updates(pshards, updates)
    with jax.named_scope("grad_allreduce"):
        new_params = jax.tree.map(_unshard, new_pshards, params)
    return new_params, new_opt_state, info
