"""Train state pytree: params, optional BN stats, optimizer state, step.

The reference kept this state implicit inside Keras/Horovod (SURVEY.md L2/H1:
model weights + replicated optimizer slots, synced by broadcast at start).
Here it is an explicit pytree, so sharding it (replicated today; optionally
optimizer-state-sharded over the data axis later, SURVEY.md §2.4 ZeRO row) is
a matter of NamedSharding annotations, and checkpointing is orbax on the
whole pytree (SURVEY.md §5.4).

Initial-weight sync across hosts is free by construction: every process
builds params from the same PRNG key, so there is no broadcast step (the
reference needed ``hvd.broadcast_global_variables``, SURVEY.md H1).
"""

from __future__ import annotations

from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax

from batchai_retinanet_horovod_coco_tpu.obs import trace


def model_variables(state: "TrainState") -> dict[str, Any]:
    """Flax variables dict for ``model.apply`` from a TrainState.

    The single place that knows which variable collections exist; forward
    paths (train step, eval forward, detection) all assemble through here so
    a new collection (e.g. EMA params) propagates everywhere at once.
    """
    variables: dict[str, Any] = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    return variables


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    batch_stats: Any  # empty dict for GN models
    opt_state: Any
    # Static (non-pytree) fields:
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    # Comm subsystem state (ISSUE 13): gradient-compression error-feedback
    # residuals, keyed per bucket (DP) or per leaf (ZeRO) — flat arrays
    # sharded over the data axis like ZeRO optimizer state, and
    # checkpointed/resharded the same way (comm/compress.py).  Empty for
    # every run without compression, in which case it contributes no
    # pytree leaves and the compiled step is unchanged.
    comm_state: Any = ()

    def apply_gradients(
        self,
        grads: Any,
        new_batch_stats: Any | None = None,
        *,
        loss_value: jnp.ndarray | None = None,
        grad_norm: jnp.ndarray | None = None,
    ):
        """One optimizer update.

        ``loss_value`` (the replica-identical pmean-ed loss) is forwarded to
        extra-args transforms — optax.contrib.reduce_on_plateau consumes it
        as ``value`` (train/optim.py "plateau" schedule); plain transforms
        never see it.  ``grad_norm`` (the step's precomputed global norm)
        likewise reaches ``clip_by_global_norm_precomputed`` so the metric
        and the clip share one reduction (obs/numerics.py contract).
        """
        if isinstance(self.tx, optax.GradientTransformationExtraArgs) and (
            loss_value is not None or grad_norm is not None
        ):
            extra = {}
            if loss_value is not None:
                extra["value"] = loss_value
            if grad_norm is not None:
                extra["grad_norm"] = grad_norm
            updates, new_opt_state = self.tx.update(
                grads, self.opt_state, self.params, **extra
            )
        else:
            updates, new_opt_state = self.tx.update(
                grads, self.opt_state, self.params
            )
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            batch_stats=(
                new_batch_stats if new_batch_stats is not None else self.batch_stats
            ),
            opt_state=new_opt_state,
        )


def create_train_state(
    model,
    tx: optax.GradientTransformation,
    example_image_shape: tuple[int, int, int, int],
    rng: jax.Array,
    init_opt_state: bool = True,
    example_dtype=jnp.float32,
) -> TrainState:
    """Initialize params; identical on every process (same PRNG key).

    The example the model is initialised on is zeros of
    ``example_image_shape`` in ``example_dtype``: an image, or, for a task
    whose ``example_dtype`` is an integer (train/task.py), token ids.

    ``model.init`` is wrapped in jit: eager init dispatches thousands of tiny
    ops, each its own host round trip and its own small compile; jitted it
    is one program (found in the compile cache on the next run).

    ``init_opt_state=False`` leaves ``opt_state`` empty: weight-update-
    sharded mode (parallel/zero.py) initializes its 1/N layout directly and
    must not pay the peak memory of a throwaway replicated ``tx.init``.
    """
    # A phase of the set-up record (obs/trace.py): the jitted init's trace,
    # lowering and load or compile land beneath it.  Under a caller's own
    # jit it covers the trace alone, and lies beneath that jit's.
    with trace.phase("init_state"):
        variables = jax.jit(model.init)(rng, jnp.zeros(example_image_shape, example_dtype))
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=tx.init(params) if init_opt_state else (),
            tx=tx,
        )
