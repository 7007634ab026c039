"""The SPMD train step: ONE jit-compiled program per shape bucket.

This is the TPU-native replacement for the reference's entire per-step stack
(SURVEY.md call stack 3.4): Keras ``train_function`` forward/backward +
``hvd.DistributedOptimizer``'s per-tensor NCCL ring allreduce.  Here the
whole thing — forward, on-device target assignment, losses, backward,
``lax.pmean`` gradient allreduce over the ``data`` mesh axis, and the
optimizer update — is one XLA program built with ``shard_map``; XLA compiles
the pmean into ICI collectives and overlaps them with backward compute (the
compile-time analogue of Horovod's tensor-fusion buffer, SURVEY.md H2).

Anchors enter as a compile-time constant (ops/anchors.py), and target
assignment (IoU + argmax matching) runs on device under ``stop_gradient``,
per the north star (BASELINE.json:5).
"""

from __future__ import annotations

import re
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
from batchai_retinanet_horovod_coco_tpu.data import pipeline as pipeline_lib
from batchai_retinanet_horovod_coco_tpu.obs import numerics as numerics_lib
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
from batchai_retinanet_horovod_coco_tpu.ops import anchors as anchors_lib
from batchai_retinanet_horovod_coco_tpu.ops import matching as matching_lib
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import DATA_AXIS
from batchai_retinanet_horovod_coco_tpu.train.state import TrainState, model_variables
from batchai_retinanet_horovod_coco_tpu.train.task import (  # noqa: F401  (re-exported)
    DetectionTask,
    LossFn,
    _forward_and_loss,
)

# The step program's slices, written down once: every ``jax.named_scope`` a
# train step enters (here, in models/, parallel/zero.py and comm/overlap.py)
# bears one of these names, and whoever reads a device trace by slice
# (``scope_table``, the benchmark's readers) files an operation under the
# OUTERMOST of them on its ``op_name``.  slice -> the scopes beneath it.
# Scopes are metadata: the lowered program is the same with and without.
# The backward needs none of its own (``transpose(jvp(<scope>))``).
STEP_SCOPES: dict[str, tuple[str, ...]] = {
    # the detection task's (train/task.py::DetectionTask.scopes)
    "backbone": ("stem", "stage2", "stage3", "stage4", "stage5"),
    "fpn": (),
    "heads": ("cls", "box"),
    "assign": (),  # anchor targets, Pallas or jnp
    # the language-model task's, by model (``model.scopes``):
    # models/granite_hybrid.py
    "embed": (),
    "mamba": ("in_proj", "conv", "ssd", "gate_norm", "out_proj"),  # with its norm
    # beneath it: the first four in models/keye_vl2.py alone, the last two in models/afmoe.py alone
    "attention": ("indexer", "select", "attention_core", "indexer_loss", "window_core", "full_core"),
    "mlp": (),
    "lm_head": (),  # final norm, head (tied or not), 1 / logits_scaling
    # models/deepseek_v2.py (``embed`` and ``lm_head`` as above)
    "mla": ("q_proj", "kv_a", "kv_b", "rope", "core", "o_proj"),  # latent attention with its norm
    "dense_mlp": (),
    "moe": ("router", "dispatch", "experts", "combine", "shared", "aux"),  # ops/moe.py, with its norm
    # models/nemotron_h.py enters ``embed``, ``mamba``, ``attention``, ``moe`` (without ``aux``), ``lm_head``:
    # every layer ONE of the three mixers with its norm, and no ``mlp``
    # models/keye_vl2.py enters ``embed``, ``attention`` (with the indexer, the selection, the selected attention and
    # the indexer's loss beneath it: ops/sparse_attention.py), ``moe`` (without ``shared``), ``lm_head``
    # models/olmo_hybrid.py enters ``embed``, ``gdn``, ``attention``, ``mlp``, ``lm_head``: a layer's norm is on its
    # sublayer's OUTPUT and counts with the sublayer
    "gdn": ("in_proj", "conv", "delta_rule", "gate_norm", "out_proj"),  # gated delta rule (ops/delta_rule.py)
    # models/afmoe.py enters ``embed``, ``attention`` (around the call of ops/attention.py ``window_core`` in a layer
    # with a sliding window, ``full_core`` in one without), ``dense_mlp``, ``moe`` (without ``aux``), ``lm_head``: the
    # norms on a sublayer's input AND output count with the sublayer
    # every task's
    "loss": (),  # focal, smooth-L1, target encoding; next-token cross-entropy
    "optimizer": (),  # clip, decay, momentum, apply, the numerics summary
    # every pmean / psum / reduce-scatter / gather of gradients, metrics and
    # batch statistics in sharded_step, zero_step and comm_step (the spatial
    # step has none of its own: GSPMD derives its collectives from the
    # operations it partitions, and they keep those operations' scopes)
    "grad_allreduce": (),
}
UNSCOPED = "unscoped"

# One instruction of ``Compiled.as_text()``: its name, and its op_name if
# it carries metadata.
_HLO_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?(?:metadata=\{[^}]*?op_name="([^"]*)"|$)'
)
# A kernel call whose frontend attributes hold newlines (a Pallas call given
# ``metadata=``) runs over several lines of text: its metadata opens a later one.
_HLO_CONTINUED_METADATA = re.compile(r'^\}+, metadata=\{[^}]*?op_name="([^"]*)"')
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")


def scope_of(op_name: str) -> tuple[str, str, str]:
    """``(slice, direction, path)`` of one HLO ``op_name``
    (``jit(train_step)/transpose(jvp(RetinaNet))/backbone/stage2/...``):
    the outermost ``STEP_SCOPES`` name on it (``UNSCOPED`` if none),
    ``bwd`` if a ``transpose(`` is on it else ``fwd``, and the names from
    the slice down with their transforms peeled off."""
    names = []
    for part in op_name.split("/"):
        while (m := _TRANSFORM.match(part)) is not None:
            part = m.group(1)
        names.append(part)
    direction = "bwd" if "transpose(" in op_name else "fwd"
    for i, name in enumerate(names):
        if name in STEP_SCOPES:
            return name, direction, "/".join(names[i:])
    return UNSCOPED, direction, "/".join(names)


def scope_table(compiled) -> dict[str, tuple[str, str, str]]:
    """``{instruction name: (slice, direction, path)}`` of a compiled step
    (a ``jax.stages.Compiled``, e.g. ``train/loop.py::compiled_step()``).

    The device trace names an operation by its HLO instruction
    (``fusion.1992``) and carries no scope; the optimized module keeps each
    instruction's ``op_name``, so the join is by name.  Parsed once from
    ``compiled.as_text()``, every computation of the module (a fusion's
    inner instructions are never traced on their own, and harmless).  A
    fusion carries ONE op_name: an update fused into a weight-gradient
    convolution counts with that convolution.  Instructions without
    metadata (parameter copies) are ``UNSCOPED``.  An executable that came
    out of a compile cache filled before the scopes existed has the OLD
    metadata (the cache key leaves metadata out)."""
    table = {}
    unfinished = None  # the instruction whose text the next lines continue
    for line in compiled.as_text().splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is not None:
            table[m.group(1)] = scope_of(m.group(2) or "")
            unfinished = m.group(1) if m.group(2) is None and line.endswith("{") else None
        elif unfinished is not None and (m := _HLO_CONTINUED_METADATA.match(line)) is not None:
            table[unfinished] = scope_of(m.group(1))
            unfinished = None
    return table


def _make_local_step(loss_fn: LossFn):
    """The per-shard (or single-device) grad computation every step shares."""

    def local_step(state: TrainState, batch: dict[str, Any]):
        (_, (metrics, new_bs)), grads = jax.value_and_grad(
            lambda p: loss_fn(state, p, batch), has_aux=True,
        )(state.params)
        return grads, metrics, new_bs

    return local_step


def _cached_step_entry(make_step: Callable) -> Callable:
    """Lazy per-structure compile cache + AOT surface, shared by the
    ZeRO and comm step flavors.

    The shard_map spec trees depend on the state's tree structure, which
    only the caller's state knows — so the step is built lazily per
    structure and cached, keyed on the treedefs (a structurally
    different state, e.g. a swapped optimizer or a comm-policy change,
    gets fresh partition specs instead of stale ones).  The ``.lower``
    attribute is the AOT point the loop's multi-process compile barrier
    uses (train/loop.py::_compile_barrier)."""
    cache: dict[Any, Callable] = {}

    def get_step(state: TrainState) -> Callable:
        key = (
            jax.tree.structure(state.opt_state),
            jax.tree.structure(state.params),
            jax.tree.structure(state.batch_stats),
            jax.tree.structure(state.comm_state),
        )
        if key not in cache:
            cache[key] = make_step(state)
        return cache[key]

    def entry(state: TrainState, batch: dict[str, Any]):
        return get_step(state)(state, batch)

    entry.lower = lambda state, batch: get_step(state).lower(state, batch)
    return entry


def _grad_norm(grads, metrics):
    """SURVEY.md §5.5: grad-norm is a first-class per-step metric —
    computed ONCE (pre-clip, on the gradients the optimizer consumes) and
    fed to the clip chain via extra args (clip_by_global_norm_precomputed),
    so the recorded value IS the norm the clip acted on, never a
    recomputation."""
    metrics["grad_norm"] = gnorm = optax.global_norm(grads)
    return gnorm


def _update(state: TrainState, grads, gnorm, metrics, new_bs, numerics) -> TrainState:
    """The replicated update the single-device, spatial and data-parallel
    steps share: apply, the post-update norm and the numerics summary;
    ``metrics`` gains its entries in place."""
    new_state = state.apply_gradients(
        grads, new_bs, loss_value=metrics["loss"], grad_norm=gnorm
    )
    # Norm of the POST-update params: the loss was computed from the
    # pre-update params, so it cannot witness a poisoned update — this
    # can, and the loop checks it before any checkpoint save (a norm read
    # of params the next step reloads anyway; cost is noise).
    metrics["param_norm"] = optax.global_norm(new_state.params)
    if numerics.enabled:
        # In-step numerics summary (ISSUE 10): ~2 extra reduces; the
        # disabled step's HLO is unchanged (trace-time Python gate).
        # Post-allreduce grads + params are replicated on a mesh, so the
        # shared summary is replicated-out safe there.
        metrics.update(
            numerics_lib.step_summary(
                grads, state.params, new_state.params,
                metrics["param_norm"], numerics,
            )
        )
    return new_state


def _global_math_step(local_step, numerics: NumericsConfig | None = None):
    """Plain global-batch step body: grads → metrics → update.

    Serves both the single-device step (jit) and the spatially partitioned
    step (jit + sharding constraints, where GSPMD turns the global
    reductions into collectives) — ONE definition so metrics/update changes
    cannot drift between them.
    """
    numerics = numerics or NumericsConfig()

    def train_step(state: TrainState, batch: dict[str, Any]):
        grads, metrics, new_bs = local_step(state, batch)
        with jax.named_scope("optimizer"):
            gnorm = _grad_norm(grads, metrics)
            new_state = _update(state, grads, gnorm, metrics, new_bs, numerics)
        return new_state, metrics

    return train_step


def make_train_step(
    model,
    image_hw: tuple[int, int],
    num_classes: int,
    mesh: Mesh | None = None,
    loss_config: losses_lib.LossConfig = losses_lib.LossConfig(),
    matching_config: matching_lib.MatchingConfig = matching_lib.MatchingConfig(),
    anchor_config: anchors_lib.AnchorConfig | None = None,
    donate_state: bool = True,
    shard_weight_update: bool = False,
    comm=None,
    topology=None,
    numerics: NumericsConfig | None = None,
    task=None,
) -> Callable[[TrainState, dict[str, Any]], tuple[TrainState, dict[str, jnp.ndarray]]]:
    """Build the jitted train step for one shape bucket.

    ``task`` (train/task.py) says what is trained: its batch fields and its
    loss.  Unset, it is detection from ``num_classes``, ``loss_config``,
    ``matching_config`` and ``anchor_config``, and ``image_hw`` is the
    task's bucket; every flavour below differentiates the task's loss.

    ``mesh``: the step is a ``shard_map`` over it; each device consumes
    batch/n_devices examples, gradients and metrics are ``lax.pmean``-ed
    over the ``data`` axis and every device applies the same update to its
    replicated state.  Unset: a single-device jit.

    ``donate_state``: the step donates its input state's buffers.

    ``shard_weight_update`` (requires ``mesh``): gradients reduce-scatter,
    each device updates its 1/N of the params with its 1/N optimizer-state
    shard, updated params all-gather back (parallel/zero.py).
    ``state.opt_state`` must come from ``init_sharded_opt_state`` and
    ``state.tx`` from ``make_optimizer(..., shard_clip_axis=DATA_AXIS)`` so
    clipping uses the global norm.

    ``comm`` (a ``comm.CommConfig``; requires ``mesh``): the gradient-
    communication policy.  The all-reduce becomes the bucketed int8/bf16
    scheme of ``comm/compress.py`` (exact f32 reduce-scatter, error
    feedback from ``state.comm_state`` where the state carries it,
    compressed gather; with ``comm.overlap`` each stage's collective is
    issued inside the backward, ``comm/overlap.py``).  With
    ``shard_weight_update`` the gradient reduce-scatter stays exact and
    the update gather is compressed.  ``grad_norm`` is taken on the
    dequantized gradients, and ``ef_residual_norm`` / ``ef_saturation`` /
    ``comm_compressed_bytes`` join the metrics.  Unset or
    ``compress="none"``: the exact step, the same program.

    ``topology`` (a ``parallel.mesh.CommTopology``): the slice x
    intra-slice grouping.  With more than one slice and per-hop modes
    that differ, the collective is the hierarchical tree (exact within a
    slice, compressed across slices; ``comm_ici_bytes`` /
    ``comm_dcn_bytes``); otherwise the flat tree at the effective mode,
    the same program as with no topology.  The mesh must be built with
    the same topology (``make_mesh(..., topology)``).  A
    ``shard_weight_update`` step ignores it, with a warning.

    ``numerics`` (obs/numerics.py): the fused in-step numerics summary —
    update/param ratio, non-finite gradient count, per-group norms and, on
    a mesh, the cross-replica agreement probe.  Unset: the same program
    as without it.

    The returned callable takes ``(state, batch)``, where ``batch`` holds
    the task's fields with the GLOBAL batch on the leading axis, and
    returns ``(new_state, metrics)``.
    """
    numerics = numerics or NumericsConfig()
    if task is None:
        task = DetectionTask(num_classes, loss_config, matching_config, anchor_config)
    if mesh is not None and not task.supports_mesh:
        raise ValueError(
            f"the {task.name} task trains on one device: its step has no "
            "sharding yet (train/task.py)"
        )
    if shard_weight_update and mesh is None:
        raise ValueError("shard_weight_update requires a mesh")
    # Hop-policy resolution (ISSUE 16): the hierarchical tree engages
    # only for a real multi-slice topology with distinct per-hop modes;
    # every other case resolves to the flat tree BEFORE tracing so the
    # degenerate paths compile byte-identical HLO.
    comm_topology = None
    if comm is not None and topology is not None:
        if shard_weight_update:
            if comm.hierarchical_with(topology):
                import warnings

                warnings.warn(
                    "comm topology has no effect with "
                    "shard_weight_update: the ZeRO path compresses the "
                    "post-update gather, which stays flat — the "
                    "hierarchical tree is a DP-path mechanism"
                )
        elif comm.hierarchical_with(topology):
            comm_topology = topology
        else:
            comm = comm.flat_equivalent(topology)
    comm_on = comm is not None and comm.enabled
    if comm_on and mesh is None:
        raise ValueError("comm compression requires a mesh")
    if comm_topology is not None and mesh is not None:
        if comm_topology.num_devices != mesh.size:
            raise ValueError(
                f"topology is {comm_topology.num_slices}x"
                f"{comm_topology.slice_size} = "
                f"{comm_topology.num_devices} devices but the mesh has "
                f"{mesh.size}"
            )
    if comm_on and comm.overlap and shard_weight_update:
        # The ZeRO flavor's compressed collective is the POST-update
        # gather — there is no backward-stage collective for overlap to
        # move.  Warn loudly rather than let the flag silently no-op.
        import warnings

        warnings.warn(
            "comm.overlap has no effect with shard_weight_update: the "
            "ZeRO path compresses the post-update gather, not the "
            "backward-pass gradient collectives (comm/overlap.py is a "
            "DP-path mechanism)"
        )
    loss_fn = task.loss_fn(model, image_hw)
    local_step = _make_local_step(loss_fn)

    if mesh is None:
        return jax.jit(
            _global_math_step(local_step, numerics),
            donate_argnums=(0,) if donate_state else (),
        )

    batch_spec = {k: P(DATA_AXIS) for k in task.batch_fields}

    if shard_weight_update:
        from batchai_retinanet_horovod_coco_tpu.parallel import zero

        if comm_on:
            from batchai_retinanet_horovod_coco_tpu.comm import (
                compress as compress_lib,
            )

        @jax.named_scope("grad_allreduce")
        def reduce_metrics(metrics):
            num_pos = lax.psum(metrics["num_pos"], DATA_AXIS)
            metrics = lax.pmean(metrics, DATA_AXIS)
            metrics["num_pos"] = num_pos
            return metrics

        def state_specs(state: TrainState) -> TrainState:
            """Per-leaf spec tree: everything replicated except opt_state
            (and the comm EF residuals, which shard the same way)."""
            return TrainState(
                step=P(),
                params=jax.tree.map(lambda _: P(), state.params),
                batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
                opt_state=zero.opt_state_partition_specs(state.opt_state),
                tx=state.tx,
                comm_state=jax.tree.map(
                    lambda _: P(DATA_AXIS), state.comm_state
                ),
            )

        def make_zero_step(state_template: TrainState):
            specs = state_specs(state_template)
            zplan = (
                compress_lib.plan_buckets(state_template.params, comm)
                if comm_on
                else None
            )

            @partial(
                shard_map,
                mesh=mesh,
                in_specs=(specs, batch_spec),
                out_specs=(specs, P()),
                check_vma=False,
            )
            def zero_step(state: TrainState, batch: dict[str, Any]):
                grads, metrics, new_bs = local_step(state, batch)
                if numerics.enabled and numerics.replica_agreement:
                    # Cross-replica probe on the LOCAL pre-reduce grads:
                    # a desynced replica's local norm diverges from its
                    # peers' long before the (averaged) loss shows it.
                    metrics["replica_agreement"] = (
                        numerics_lib.replica_agreement(
                            optax.global_norm(grads), DATA_AXIS
                        )
                    )
                metrics = reduce_metrics(metrics)
                if state.batch_stats:
                    with jax.named_scope("grad_allreduce"):
                        new_bs = lax.pmean(new_bs, DATA_AXIS)
                # Reduce-scatter + sharded update + all_gather replaces the
                # pmean-allreduce + replicated update (parallel/zero.py).
                # Comm-on (ISSUE 13): the gradient reduce-scatter stays
                # exact (it feeds the sharded optimizer and the global
                # clip norm); the f32 param gather is replaced by the
                # bucketed compressed UPDATE gather with per-leaf EF
                # residuals (comm/compress.zero_gather_updates).
                comm_out: dict[str, Any] = {}
                gather = None
                if comm_on:
                    comm_cs = (
                        state.comm_state
                        if isinstance(state.comm_state, dict)
                        else {}
                    )

                    @jax.named_scope("grad_allreduce")
                    def gather(updates, params):
                        new_p, new_res, sat = (
                            compress_lib.zero_gather_updates(
                                updates, params, comm_cs, zplan, comm,
                                DATA_AXIS, mesh.size,
                            )
                        )
                        comm_out["res"] = new_res
                        comm_out["sat"] = sat
                        return new_p

                new_params, new_opt, info = zero.sharded_update(
                    state.tx,
                    grads,
                    state.opt_state,
                    state.params,
                    n=mesh.size,
                    loss_value=metrics["loss"],
                    gather_updates=gather,
                )
                metrics.update(info)
                with jax.named_scope("optimizer"):
                    # Post-update param norm (see the single-device step): the
                    # gathered new_params are replicated, so the norm is too.
                    metrics["param_norm"] = optax.global_norm(new_params)
                    if numerics.enabled:
                        # Hand-assembled summary: the reduced gradient only
                        # ever exists as 1/N shards here, so the non-finite
                        # count psums the LOCAL counts (a NaN anywhere
                        # poisons the reduce-scatter, so local detection is
                        # global detection) and group norms are the pmean of
                        # per-replica local-grad norms; params are
                        # replicated, so the update ratio is the same math
                        # as the replicated step's.
                        metrics["nonfinite_grads"] = lax.psum(
                            numerics_lib.nonfinite_count(grads), DATA_AXIS
                        )
                        metrics["update_ratio"] = numerics_lib.update_ratio(
                            state.params, new_params, metrics["param_norm"]
                        )
                        if numerics.per_group:
                            for key, norm in numerics_lib.group_norms(
                                grads
                            ).items():
                                metrics[f"gnorm/{key}"] = lax.pmean(
                                    norm, DATA_AXIS
                                )
                new_comm_state = state.comm_state
                if comm_on:
                    with jax.named_scope("grad_allreduce"):
                        metrics.update(
                            compress_lib.comm_metrics(
                                zplan, comm_out["res"], comm_out["sat"],
                                DATA_AXIS, mesh.size, zero=True,
                            )
                        )
                    if isinstance(state.comm_state, dict):
                        new_comm_state = comm_out["res"]
                new_state = state.replace(
                    step=state.step + 1,
                    params=new_params,
                    batch_stats=new_bs,
                    opt_state=new_opt,
                    comm_state=new_comm_state,
                )
                return new_state, metrics

            return jax.jit(
                zero_step, donate_argnums=(0,) if donate_state else ()
            )

        return _cached_step_entry(make_zero_step)

    if comm_on:
        # Comm subsystem path (ISSUE 13): bucketed compressed all-reduce
        # with error feedback, optionally staged inside the backward pass
        # (comm/overlap.py).  A separate shard_map flavor — the exact
        # path below stays byte-identical to pre-ISSUE-13.
        from batchai_retinanet_horovod_coco_tpu.comm import (
            compress as compress_lib,
        )
        from batchai_retinanet_horovod_coco_tpu.comm import (
            overlap as overlap_lib,
        )

        def make_comm_step(state_template: TrainState):
            plan = compress_lib.plan_buckets(
                state_template.params, comm, comm_topology
            )
            spec = TrainState(
                step=P(),
                params=jax.tree.map(lambda _: P(), state_template.params),
                batch_stats=jax.tree.map(
                    lambda _: P(), state_template.batch_stats
                ),
                opt_state=jax.tree.map(
                    lambda _: P(), state_template.opt_state
                ),
                tx=state_template.tx,
                comm_state=jax.tree.map(
                    lambda _: P(DATA_AXIS), state_template.comm_state
                ),
            )
            grad_fn = (
                overlap_lib.make_overlap_grad_fn(
                    plan, comm, DATA_AXIS, mesh.size, comm_topology
                )
                if comm.overlap
                else None
            )

            @partial(
                shard_map,
                mesh=mesh,
                in_specs=(spec, batch_spec),
                out_specs=(spec, P()),
                check_vma=False,
            )
            def comm_step(state: TrainState, batch: dict[str, Any]):
                comm_cs = (
                    state.comm_state
                    if isinstance(state.comm_state, dict)
                    else {}
                )
                if comm.overlap:
                    # Each stage's compressed collective fires inside
                    # the backward via the custom-vjp taps; the grads
                    # come out ALREADY reduced and the EF residuals /
                    # saturation ride the cotangent channel.  (The
                    # replica-agreement probe needs local pre-reduce
                    # grads, which this schedule never materializes as
                    # one tree — structurally absent here.)
                    def loss_of_params(p):
                        return loss_fn(state, p, batch)

                    (_, (metrics, new_bs)), grads, new_comm, sat = (
                        grad_fn(loss_of_params, state.params, comm_cs)
                    )
                else:
                    grads, metrics, new_bs = local_step(state, batch)
                    if numerics.enabled and numerics.replica_agreement:
                        metrics["replica_agreement"] = (
                            numerics_lib.replica_agreement(
                                optax.global_norm(grads), DATA_AXIS
                            )
                        )
                    # One fused pass: exact f32 reduce-scatter + EF
                    # add-back + compressed gather per bucket.
                    with jax.named_scope("grad_allreduce"):
                        grads, new_comm, sat = compress_lib.reduce_tree(
                            grads, comm_cs, plan, comm, DATA_AXIS,
                            mesh.size, comm_topology,
                        )
                with jax.named_scope("grad_allreduce"):
                    num_pos = lax.psum(metrics["num_pos"], DATA_AXIS)
                    metrics = lax.pmean(metrics, DATA_AXIS)
                    metrics["num_pos"] = num_pos
                with jax.named_scope("optimizer"):
                    # On the DEQUANTIZED gradients — the values the
                    # optimizer actually consumes.
                    gnorm = _grad_norm(grads, metrics)
                if state.batch_stats:
                    with jax.named_scope("grad_allreduce"):
                        new_bs = lax.pmean(new_bs, DATA_AXIS)
                with jax.named_scope("optimizer"):
                    new_state = state.apply_gradients(
                        grads, new_bs, loss_value=metrics["loss"],
                        grad_norm=gnorm,
                    )
                    metrics["param_norm"] = optax.global_norm(
                        new_state.params
                    )
                with jax.named_scope("grad_allreduce"):
                    metrics.update(
                        compress_lib.comm_metrics(
                            plan, new_comm, sat, DATA_AXIS, mesh.size,
                            topology=comm_topology,
                        )
                    )
                if isinstance(state.comm_state, dict):
                    new_state = new_state.replace(comm_state=new_comm)
                if numerics.enabled:
                    with jax.named_scope("optimizer"):
                        metrics.update(
                            numerics_lib.step_summary(
                                grads, state.params, new_state.params,
                                metrics["param_norm"], numerics,
                            )
                        )
                return new_state, metrics

            return jax.jit(
                comm_step, donate_argnums=(0,) if donate_state else ()
            )

        # Lazy per-structure cache + AOT surface, shared with the ZeRO
        # flavor: the comm-state tree structure is the caller's (empty
        # for the stateless deprecated alias, per-bucket dict with EF).
        return _cached_step_entry(make_comm_step)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def sharded_step(state: TrainState, batch: dict[str, Any]):
        grads, metrics, new_bs = local_step(state, batch)
        if numerics.enabled and numerics.replica_agreement:
            # Cross-replica probe BEFORE the allreduce: per-replica local
            # norms vs the axis min/max — the silent-desync detector the
            # averaged gradients cannot provide (obs/numerics.py).
            metrics["replica_agreement"] = numerics_lib.replica_agreement(
                optax.global_norm(grads), DATA_AXIS
            )
        with jax.named_scope("grad_allreduce"):
            # THE allreduce: Horovod's NCCL ring → one compiled pmean over ICI.
            grads = lax.pmean(grads, DATA_AXIS)
            num_pos = lax.psum(metrics["num_pos"], DATA_AXIS)  # a count, not a mean
            metrics = lax.pmean(metrics, DATA_AXIS)
            metrics["num_pos"] = num_pos
        with jax.named_scope("optimizer"):
            gnorm = _grad_norm(grads, metrics)
        if state.batch_stats:
            with jax.named_scope("grad_allreduce"):
                new_bs = lax.pmean(new_bs, DATA_AXIS)  # sync-BN semantics
        with jax.named_scope("optimizer"):
            new_state = _update(state, grads, gnorm, metrics, new_bs, numerics)
        return new_state, metrics

    return jax.jit(sharded_step, donate_argnums=(0,) if donate_state else ())


def _degenerate_strided_conv_heights(
    image_h: int, num_space: int
) -> list[int]:
    """Stride-2 3x3 conv input heights inside the XLA weight-grad bug zone.

    The model family's stride-2 3x3 convs consume maps at H/4, H/8, H/16
    (ResNet stage3/4/5 ``conv2``), H/32 (FPN P6 reads C5) and H/64 (P7
    reads P6).  Empirical risk zone (see make_train_step_spatial): shards
    >= 8 AND rows-per-shard in [0.5, 2) — 2 rows/shard and the
    replication-handled H < num_space/2 maps measured exact, as did every
    layout at <= 4 shards (including exactly 1 row/shard, which IS broken
    at 8 shards; the boundary is shard-count-dependent, so the canary test
    pins both sides of it).

    Round-5 16-shard sweep (strided_conv_weight_grad.py --probe, pinned
    by test_xla_strided_conv_grad_canary_16shard): broken layouts at 16
    shards are rows/shard in {0.5, 1} (44%/41%), with 1.5, 2 and the
    replicated 0.25 rows exact — every broken layout falls inside this
    zone, so the [n/2, 2n) rule is MEASURED (as a conservative superset;
    1.5 rows over-refuses) at 4, 8 and 16 shards rather than
    extrapolated.
    """
    if num_space < 8:
        return []
    # Ceil division: the stride-2 downsample chain produces ceil(H/d)
    # extents (SAME padding), and at the zone's lower edge floor is one
    # row short — e.g. H=224, 8 shards: floor gives 3 (outside [4, 16))
    # but the real P7 input is ceil(224/64)=4, the measured-wrong
    # 0.5-rows-per-shard layout.
    heights = [-(-image_h // d) for d in (4, 8, 16, 32, 64)]
    return [h for h in heights if num_space / 2 <= h < 2 * num_space]


# Backbones whose spatial-step gradients measured EXACT on the virtual
# mesh rig (round-5 f64 probes).  Deep backbones are NOT on the list: see
# make_train_step_spatial's "Data-axis envelope" docstring section.
_SPATIAL_GRAD_VALIDATED_BACKBONES = frozenset({"resnet_test"})


def _data_axis_risky_stage_heights(image_h: int, num_space: int) -> list[int]:
    """Backbone-stage map heights inside the round-5 residual-chain bug
    zone: stages run at ceil(H/4..H/32), and the measured model-level
    divergence (see make_train_step_spatial's "Data-axis envelope")
    requires some residual-stage map at <= 1 row per shard — hw-64
    models (min stage rows 0.5-1) diverge at data >= 2 while hw-256
    models (min 4 rows) measure clean, matching the minimal repro's
    boundary (1 row broken at space=2; 1.5+ rows exact)."""
    if num_space < 2:
        return []
    heights = [-(-image_h // d) for d in (4, 8, 16, 32)]
    return [h for h in heights if h <= num_space]


def make_train_step_spatial(
    model,
    image_hw: tuple[int, int],
    num_classes: int,
    mesh: Mesh,
    loss_config: losses_lib.LossConfig = losses_lib.LossConfig(),
    matching_config: matching_lib.MatchingConfig = matching_lib.MatchingConfig(),
    anchor_config: anchors_lib.AnchorConfig | None = None,
    donate_state: bool = True,
    allow_degenerate_spatial_sharding: bool = False,
    allow_unvalidated_bf16: bool = False,
    allow_data_axis_divergence: bool = False,
    numerics: NumericsConfig | None = None,
) -> Callable[[TrainState, dict[str, Any]], tuple[TrainState, dict[str, jnp.ndarray]]]:
    """Train step with the IMAGE sharded across chips (spatial partitioning).

    The training-side analogue of sequence/context parallelism
    (SURVEY.md §5.7, same idea as ``evaluate.detect.make_detect_fn_spatial``):
    the batch shards over ``data`` AND each image's H axis shards over
    ``spatial_axis``, so a 2-D mesh trains images too large (or batches too
    small) for pure DP.  Built with ``jit`` + sharding constraints, not
    ``shard_map``: spatially partitioned convs need GSPMD's halo-exchange
    machinery — ring-attention's "pass the boundary" pattern, compiled
    automatically — which per-device code would have to hand-roll.

    The step body is the plain single-device global-batch math (no
    explicit pmean): under GSPMD the compiler partitions the forward,
    inserts the halos, and turns the global loss/gradient reductions into
    the right collectives.  Within the supported sharding envelope (below)
    gradients match the single-device step to 1e-5-class agreement
    (pinned by tests/distributed/test_spatial_train.py).

    Sharding envelope: XLA's SPMD partitioner mis-computes the WEIGHT
    gradient of a stride-2 3x3 conv whose per-shard input extent collapses
    to ~one row (isolated repro in
    tests/distributed/test_spatial_train.py::test_xla_strided_conv_grad_canary:
    ~45% relative error on that conv's weight grad, persisting in f64 —
    a genuinely different sum, not rounding — with both the GSPMD and
    Shardy partitioners, jax 0.9.0; forward and grad-input are exact).
    The boundary is EMPIRICAL and shard-count-dependent (round-4 probes,
    pinned by the canary test): at 8 shards, 1 row/shard is badly wrong
    (44%) and half-a-row/shard measurably wrong (1e-4-class on params),
    while 2 rows/shard and the tiny H < num_space/2 maps (which the
    partitioner handles via replication) are exact to 1e-15; at <= 4
    shards every layout measured exact, INCLUDING 1 row/shard.  The
    model family's stride-2 3x3 convs consume maps of H/4, H/8, H/16
    (backbone stage3/4/5), H/32 (FPN P6 from C5) and H/64 (P7 from P6),
    so this factory REFUSES meshes with ``space >= 8`` where any of those
    heights lands in the measured risk zone
    [num_space/2, 2*num_space).  ``allow_degenerate_spatial_sharding=True``
    overrides (the parity tests use it to pin the divergence magnitude);
    expect 1e-3-class relative gradient error in the affected conv
    kernels until the upstream fix (at which point the canary test fails
    and this guard should be dropped).

    Dtype envelope: bf16 models at flagship width are MISCOMPILED by the
    SPMD partitioner under this step's shardings (round-4 finding, pinned
    by test_spatial_train.py::test_xla_bf16_spatial_step_canary): with the
    box gradient in the graph, the forward cls_loss VALUE comes out wrong
    — 1.128 → 1.420 (gn) / 2.82 (frozen_bn) with gradients 14–60x off —
    deterministically, at 256-wide heads, while f32 at the same width and
    bf16 at width 64 are exact; the wrong value changes when unrelated
    graph consumers (e.g. ``optax.global_norm(grads)``) are added, the
    signature of a partitioner miscompilation, and persists across the
    mask/custom-VJP/planar-layout variants of the loss.  Reproduced on the
    virtual CPU mesh (jax 0.9.0); real multi-chip TPU is unavailable to
    this rig, so TPU is UNVALIDATED rather than known-good.  The factory
    therefore refuses non-f32 models; ``allow_unvalidated_bf16=True``
    overrides for users who have validated their own backend (run one
    step of this factory's output against ``make_train_step(mesh=None)``
    on an identical batch first — the canary shows exactly how).

    Data-axis envelope (round-5 finding): on DEEP backbones whose
    small stages land at <= 1 row per shard, combining a data axis >= 2
    with space sharding makes the compiled backward diverge from the
    single-device gradients — measured per-step param error (f64,
    reduced-width resnet50, hw 64, so NOT rounding): L2 4.1e-6 at
    data=1, 2.8e-4 at data=2, 6.5e-4 at 4, 2.1e-3 at 8, 7.2e-3 at 16
    (~x3 per data doubling; identical at space=2 and space=4).  The
    minimal trigger is >= 2 chained residual blocks of 3x3 convs on an
    H=2 map at (data>=2, space=2) — FD-proven wrong backward, up to
    4.1e5x relative error
    (scripts/xla_repros/spatial_residual_chain_grad.py; canary:
    test_spatial_train.py::test_xla_spatial_data_axis_grad_canary).
    Clean by measurement: the shallow CI backbone everywhere,
    ``(data, 1)`` meshes (bit-exact), pure-spatial ``(1, space)``
    meshes (4e-6-class), and — key for real workloads — the SAME deep
    model at hw 256, where every stage runs >= 4 rows/shard (param L2
    5.6e-8 at (4, 2)); flagship 800-class buckets keep every stage
    >= 3 rows/shard at space <= 4 and are therefore outside the zone.
    The factory refuses data >= 2 only when some backbone-stage height
    lands at <= 1 row per shard (``_data_axis_risky_stage_heights``)
    on a non-shallow backbone; ``allow_data_axis_divergence=True``
    overrides (the dryrun uses it to pin the divergence magnitude).

    Pallas kernels are opaque to GSPMD and cannot be spatially
    partitioned: the fused assignment is forced off (the vmapped XLA
    matching path partitions fine) and a ``pallas_focal`` loss config is
    rejected rather than silently replicated.
    """
    import dataclasses as _dc

    from batchai_retinanet_horovod_coco_tpu.parallel.mesh import SPACE_AXIS

    model_dtype = jnp.dtype(model.config.dtype)
    if model_dtype != jnp.dtype(jnp.float32) and not allow_unvalidated_bf16:
        raise ValueError(
            f"spatial partitioning with a {model_dtype.name} model is "
            "refused: the SPMD partitioner miscompiles the bf16 train "
            "step at flagship width (wrong cls_loss values, 14-60x wrong "
            "gradients — see make_train_step_spatial's docstring and the "
            "bf16 spatial canary test).  Train spatially in f32 "
            "(--f32 with --spatial-shards), or pass "
            "allow_unvalidated_bf16=True after validating one step "
            "against the single-device step on your backend"
        )

    num_space = dict(mesh.shape).get(SPACE_AXIS, 1)
    if not allow_degenerate_spatial_sharding:
        risky = _degenerate_strided_conv_heights(image_hw[0], num_space)
        if risky:
            raise ValueError(
                f"space axis size {num_space} is too large for image "
                f"height {image_hw[0]}: stride-2 3x3 conv input maps of "
                f"height {risky} would land in the measured envelope "
                "where XLA's SPMD partitioner mis-computes strided-conv "
                "weight gradients (~[0.5, 2) rows per shard at >= 8 "
                "shards; see make_train_step_spatial docstring).  Use a "
                "smaller --spatial-shards for this bucket (space <= 4 is "
                "always outside the envelope), or pass "
                "allow_degenerate_spatial_sharding=True to accept "
                "1e-3-class gradient error in the affected conv kernels"
            )
    num_data = dict(mesh.shape).get(DATA_AXIS, 1)
    risky_stage = _data_axis_risky_stage_heights(image_hw[0], num_space)
    if (
        num_space > 1
        and num_data > 1
        and risky_stage
        and model.config.backbone not in _SPATIAL_GRAD_VALIDATED_BACKBONES
        and not allow_data_axis_divergence
    ):
        raise ValueError(
            f"spatial mesh (data={num_data}, space={num_space}) with "
            f"backbone {model.config.backbone!r} at image height "
            f"{image_hw[0]} is refused: backbone-stage maps of height "
            f"{risky_stage} land at <= 1 row per shard, where the "
            "partitioned backward of deep (residual-chain) backbones "
            "diverges from the single-device gradients once the data "
            "axis exceeds 1 (measured f64: 2.8e-4 per-step param L2 at "
            "data=2 growing ~3x per doubling — see "
            "make_train_step_spatial's 'Data-axis envelope').  Use a "
            "pure-spatial (1, space) mesh (device count equal to the "
            "spatial shard count), larger images (flagship 800-class "
            "buckets keep every stage >= 3 rows/shard at space <= 4 and "
            "measure clean), a plain DP mesh, or pass "
            "allow_data_axis_divergence=True to accept the measured "
            "gradient error"
        )
    if loss_config.pallas_focal:
        raise ValueError(
            "pallas_focal is incompatible with spatial partitioning: a "
            "pallas_call is opaque to GSPMD, so the head outputs would be "
            "replicated instead of sharded — use the default XLA focal path"
        )
    # fused_pallas=None would pick the GSPMD-opaque kernel on a TPU.
    matching_config = _dc.replace(matching_config, fused_pallas=False)
    # Numerics summary rides the global-math body (grads are global under
    # GSPMD); the per-replica agreement probe needs a named axis shard_map
    # does not exist here, so it is structurally absent on this path.
    task = DetectionTask(num_classes, loss_config, matching_config, anchor_config)
    train_step = _global_math_step(
        _make_local_step(task.loss_fn(model, image_hw)), numerics
    )

    from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
        spatial_batch_shardings,
    )

    rep = NamedSharding(mesh, P())
    # ONE definition of the batch layout, shared with the loop's
    # _device_batch placement (parallel/mesh.py).
    batch_shardings = spatial_batch_shardings(mesh)
    return jax.jit(
        train_step,
        in_shardings=(rep, batch_shardings),
        out_shardings=(rep, rep),
        donate_argnums=(0,) if donate_state else (),
    )


def make_eval_forward(
    model,
    mesh: Mesh | None = None,
) -> Callable[[TrainState, jnp.ndarray], dict[str, jnp.ndarray]]:
    """Jitted inference forward: images → {cls_logits, box_deltas}.

    Uses running/frozen statistics (train=False).  With a mesh, the batch is
    sharded over ``data`` and outputs gathered — XLA inserts the all_gather
    (the reference ran eval on rank 0 only, SURVEY.md M10; here every chip
    contributes).
    """

    def forward(state: TrainState, images: jnp.ndarray):
        # uint8 batches normalize on device (data/pipeline.normalize_images).
        images = pipeline_lib.normalize_images(images)
        return model.apply(model_variables(state), images, train=False)

    if mesh is None:
        return jax.jit(forward)

    sharded = shard_map(
        forward,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    return jax.jit(sharded)
