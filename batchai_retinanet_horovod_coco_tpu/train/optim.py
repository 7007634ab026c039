"""Optimizer + LR schedule factory.

Reference training hyper-surface (SURVEY.md M11/W1): Adam at a small base LR
scaled by ``hvd.size()`` (linear-scaling rule), ReduceLROnPlateau, optional
``--freeze-backbone``.  TPU-native redesign: everything is an optax chain
built ONCE — the schedule is a pure function of the step (compiled into the
train step; no callback machinery), warmup replaces the Horovod
LearningRateWarmup callback, and backbone freezing is a gradient mask rather
than layer.trainable flips (no graph rebuild).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax


def clip_by_global_norm_precomputed(
    max_norm: float,
) -> optax.GradientTransformationExtraArgs:
    """``optax.clip_by_global_norm`` that can REUSE a precomputed norm.

    The train step already computes ``optax.global_norm(grads)`` for its
    ``grad_norm`` metric (SURVEY.md §5.5); the stock optax clip then
    recomputed the identical reduction inside the chain.  This transform
    accepts the step's value via extra args (``grad_norm=...``, forwarded
    by ``optax.chain``/``multi_transform`` — TrainState.apply_gradients
    passes it), so the metric and the clip share ONE reduction, and the
    recorded pre-clip norm is BY CONSTRUCTION the norm the clip acted on
    (the numerics plane's contract, obs/numerics.py).  Without the extra
    arg it computes the norm itself — identical semantics either way
    (``scale = max_norm / max(norm, max_norm)``, the same rule as
    ``clip_by_global_norm_sharded``; equivalence pinned by
    tests/unit/test_numerics.py).

    NOT safe under ``optax.multi_transform`` masking: the masked branch
    sees only its subtree's updates, while the step's precomputed norm
    covers the FULL tree — forwarding it would clip trained params by a
    norm inflated with frozen gradients (a ~200x effective-LR collapse
    in a freeze-backbone run with large frozen grads).  ``make_optimizer``
    therefore keeps the stock self-computing clip whenever
    ``freeze_backbone`` masks the chain (pinned by test_numerics).
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None, *, grad_norm=None, **extra):
        del params, extra
        norm = optax.global_norm(updates) if grad_norm is None else grad_norm
        scale = max_norm / jnp.maximum(norm, max_norm)
        return jax.tree.map(lambda u: u * scale, updates), state

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    optimizer: str = "sgd"  # "sgd" | "adam" | "adamw"
    base_lr: float = 0.01  # per-256-global-batch for sgd (detectron rule)
    # Linear-scaling rule: effective lr = base_lr * global_batch / 256 for
    # sgd, or base_lr * world_size for adam and adamw (the reference's
    # hvd.size() rule).
    global_batch_size: int = 16
    world_size: int = 1
    warmup_steps: int = 500
    total_steps: int = 90_000
    schedule: str = "multistep"  # "multistep" | "cosine" | "constant" | "plateau"
    # Multistep: decay 10x at these fractions of total_steps (detectron 1x).
    milestones: tuple[float, ...] = (2 / 3, 8 / 9)
    # "plateau": the reference's ReduceLROnPlateau (keras-retinanet monitors
    # per-epoch training loss, factor 0.1, patience 2).  TPU-native redesign:
    # no callback — optax.contrib.reduce_on_plateau rides INSIDE the compiled
    # step, fed the pmean-ed loss, so every replica scales identically and
    # the controller state checkpoints/restores with the rest of opt_state.
    # ``plateau_window`` steps of loss are averaged per comparison (the epoch
    # analogue); patience counts windows.
    plateau_factor: float = 0.1
    plateau_patience: int = 2
    plateau_window: int = 1000
    plateau_min_delta: float = 1e-4
    momentum: float = 0.9
    # sgd: added to every leaf's gradient.  adamw: decoupled, and only for
    # the matrices (``decays``); adam: none.
    weight_decay: float = 1e-4
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    clip_global_norm: float = 10.0
    freeze_backbone: bool = False


def decays(params):
    """adamw's decay mask: leaves of two or more dimensions (embeddings,
    projections, convolution taps) decay; vectors and scalars (norm scales,
    biases, Mamba's ``A_log``, ``D`` and ``dt_bias``) do not."""
    return jax.tree.map(lambda p: jnp.ndim(p) >= 2, params)


def peak_lr(config: OptimizerConfig) -> float:
    if config.optimizer in ("adam", "adamw"):
        return config.base_lr * config.world_size
    return config.base_lr * config.global_batch_size / 256.0


def make_schedule(config: OptimizerConfig) -> optax.Schedule:
    peak = peak_lr(config)
    # join_schedules rebases the post-warmup schedule to step 0 at the join,
    # so boundaries/horizons are expressed relative to the end of warmup —
    # milestones land at the intended GLOBAL step.
    if config.schedule in ("constant", "plateau"):
        # plateau: base LR is flat; the reduce_on_plateau transform in
        # make_optimizer supplies the data-driven decay.
        sched = optax.constant_schedule(peak)
    elif config.schedule == "cosine":
        sched = optax.cosine_decay_schedule(
            peak, max(1, config.total_steps - config.warmup_steps)
        )
    elif config.schedule == "multistep":
        boundaries = {
            int(m * config.total_steps) - config.warmup_steps: 0.1
            for m in config.milestones
        }
        sched = optax.piecewise_constant_schedule(peak, boundaries)
    else:
        raise ValueError(f"unknown schedule: {config.schedule!r}")
    if config.warmup_steps > 0:
        warmup = optax.linear_schedule(
            peak / max(1, config.warmup_steps), peak, config.warmup_steps
        )
        return optax.join_schedules([warmup, sched], [config.warmup_steps])
    return sched


def make_optimizer(
    config: OptimizerConfig,
    shard_clip_axis: str | None = None,
) -> tuple[optax.GradientTransformation, optax.Schedule]:
    """(transform, schedule) — schedule returned separately for logging.

    ``shard_clip_axis``: name of the mesh axis the updates are sharded over
    (weight-update-sharded mode, parallel/zero.py).  The chain then uses
    ``clip_by_global_norm_sharded`` — same clip rule, norm psum-ed across
    shards — in the SAME chain position, so freeze-masking applies to it
    identically.  The clip value has exactly one source: this config.
    """
    schedule = make_schedule(config)
    if config.optimizer == "sgd":
        core = optax.chain(
            optax.add_decayed_weights(config.weight_decay),
            optax.sgd(schedule, momentum=config.momentum),
        )
    elif config.optimizer == "adam":
        core = optax.adam(schedule, b2=config.adam_b2, eps=config.adam_eps)
    elif config.optimizer == "adamw":
        core = optax.adamw(
            schedule, b2=config.adam_b2, eps=config.adam_eps,
            weight_decay=config.weight_decay, mask=decays,
        )
    else:
        raise ValueError(f"unknown optimizer: {config.optimizer!r}")

    # The freeze-masked chain must NOT consume the step's precomputed
    # norm: inside multi_transform the clip sees only the trained
    # subtree, and the full-tree norm (which includes the frozen
    # backbone's gradients) would silently over-clip it — see
    # clip_by_global_norm_precomputed's docstring.  The frozen chain
    # keeps the self-computing clips (extra args are dropped for plain
    # transforms, so the step's grad_norm= is harmlessly ignored).
    use_precomputed = not config.freeze_backbone
    if shard_clip_axis is not None:
        from batchai_retinanet_horovod_coco_tpu.parallel.zero import (
            clip_by_global_norm_sharded,
        )

        clip = clip_by_global_norm_sharded(
            config.clip_global_norm, shard_clip_axis,
            use_precomputed=use_precomputed,
        )
    elif use_precomputed:
        # Accepts the step's precomputed global norm via extra args so the
        # grad_norm metric and the clip share one reduction (identical
        # semantics to optax.clip_by_global_norm otherwise).
        clip = clip_by_global_norm_precomputed(config.clip_global_norm)
    else:
        clip = optax.clip_by_global_norm(config.clip_global_norm)
    tx = optax.chain(clip, core)

    if config.freeze_backbone:
        # Zero gradients for the backbone subtree (reference --freeze-backbone).
        def label(params):
            return {
                k: ("frozen" if k == "backbone" else "trained") for k in params
            }

        tx = optax.multi_transform(
            {"trained": tx, "frozen": optax.set_to_zero()}, label
        )

    if config.schedule == "plateau":
        # Appended last so the scale multiplies the whole update (= scaling
        # the LR).  The step feeds it value=loss via apply_gradients.
        tx = optax.chain(
            tx,
            optax.contrib.reduce_on_plateau(
                factor=config.plateau_factor,
                patience=config.plateau_patience,
                # rtol=0: improvement is judged against the ABSOLUTE
                # min_delta (keras ReduceLROnPlateau semantics), not optax's
                # default best_value-relative threshold.  optax rejects
                # rtol == atol == 0, so min_delta=0 (legal in keras) is
                # floored at a value far below any f32 loss resolution.
                rtol=0.0,
                atol=max(config.plateau_min_delta, 1e-12),
                accumulation_size=config.plateau_window,
            ),
        )
    return optax.with_extra_args_support(tx), schedule


def plateau_scale(opt_state) -> float | None:
    """Current ReduceLROnPlateau LR scale in ``opt_state`` (None if absent).

    Matches the controller's state node by type — a name-based search
    ("scale") collides with fields of other optax states in the chain.
    """
    plateau_state = optax.contrib.ReduceLROnPlateauState
    found = [
        x
        for x in jax.tree.leaves(
            opt_state, is_leaf=lambda x: isinstance(x, plateau_state)
        )
        if isinstance(x, plateau_state)
    ]
    return float(found[0].scale) if found else None
