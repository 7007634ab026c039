"""The training loop: the reference's ``model.fit_generator`` equivalent.

SURVEY.md call stack 3.2: Keras ``fit_generator`` + callback list
(BroadcastGlobalVariables, ModelCheckpoint, CocoEval, TensorBoard) becomes an
explicit step loop: pull a host batch, dispatch the jitted SPMD step for that
batch's shape bucket (one compiled program per bucket, cached here), log
device-averaged metrics, checkpoint/eval on schedule.  There is no broadcast
callback — initial weights are identical on every process by PRNG
construction (train/state.py) — and no RedirectModel/convert step.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import threading
import warnings
from typing import Any, Callable, Iterable, Iterator

import jax
import numpy as np
from jax.sharding import Mesh

from batchai_retinanet_horovod_coco_tpu import losses as losses_lib
from batchai_retinanet_horovod_coco_tpu.data.prefetch import prefetch_map
from batchai_retinanet_horovod_coco_tpu.ops import matching as matching_lib
from batchai_retinanet_horovod_coco_tpu.parallel.mesh import (
    SPACE_AXIS,
    batch_sharding,
    replicated_sharding,
    spatial_batch_shardings,
)
from batchai_retinanet_horovod_coco_tpu.train import optim
from batchai_retinanet_horovod_coco_tpu.train.state import TrainState
from batchai_retinanet_horovod_coco_tpu.train.step import (
    make_train_step,
    make_train_step_spatial,
)
from batchai_retinanet_horovod_coco_tpu.obs import telemetry, trace, watchdog
from batchai_retinanet_horovod_coco_tpu.obs import numerics as numerics_lib
from batchai_retinanet_horovod_coco_tpu.obs.events import EventSink, device_memory_stats
from batchai_retinanet_horovod_coco_tpu.obs.numerics import NumericsConfig
from batchai_retinanet_horovod_coco_tpu.obs.trace import monotonic_s
from batchai_retinanet_horovod_coco_tpu.train.state import model_variables
from batchai_retinanet_horovod_coco_tpu.train.task import DetectionTask
from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import CheckpointManager

# Every obs/trace.py span of this process is from here on a profiler
# annotation too ("rn.data_wait", "rn.step", "rn.device-prefetch", ...):
# obs/trace.py never imports jax, this module has it anyway.
trace.install_annotation_factory(jax.profiler.TraceAnnotation)


# With --log-every 0 the loop still pulls the loss scalar at this cadence so
# a NaN cannot train garbage for the rest of a long run before aborting
# (SURVEY.md §5.2; the log-boundary-only check was a real hole at
# log_every=0).  One scalar fetch per window is noise next to step time.
_FINITE_CHECK_EVERY = 100


# The metrics whose finiteness gates checkpointing: ``loss`` witnesses the
# pre-update params, ``param_norm`` the post-update ones (a save at the very
# step whose update introduced the poison is only caught by the latter —
# the step-N loss is computed before the step-N update, train/step.py).
_SENTINEL_METRICS = ("loss", "param_norm")


def _abort_nonfinite(
    name: str,
    value: float,
    step: int,
    cadence: str,
    *,
    model=None,
    state=None,
    device_arrays: dict[str, Any] | None = None,
    image_ids=None,
    metrics=None,
    rng_seed: int | None = None,
    dump_dir: str | None = None,
    logger=None,
) -> None:
    """Numerical sanitizer abort (SURVEY.md §5.2), ISSUE-10 edition: run
    the provenance pass IN-PLACE on the already-poisoned state/batch and
    land ONE NUMERICS_DUMP.json before raising — no ``--debug-nans``
    rerun needed.  The dump can never mask the abort: a failing
    provenance pass degrades to one structured ``numerics_dump_error``
    stderr line and the original FloatingPointError still raises."""
    dump_path = None
    first = None
    try:
        dump = numerics_lib.provenance(
            step=step,
            metrics=metrics,
            params=state.params if state is not None else None,
            model=model,
            variables=(
                model_variables(state)
                if model is not None and state is not None
                else None
            ),
            images=(device_arrays or {}).get("images"),
            image_ids=image_ids,
            rng_seed=rng_seed,
            tripped={"metric": name, "value": float(value)},
            cadence=cadence,
        )
        first = dump.get("first_nonfinite")
        # The file needs a configured home (--obs-dir / --log-dir / the
        # LoopConfig field) — a bare run still gets the localization in
        # the exception message, but never litters the cwd.
        target_dir = dump_dir or trace.trace_dir()
        if target_dir:
            dump_path = numerics_lib.write_dump(dump, target_dir)
    except Exception as e:  # the abort must land with or without a dump
        print(
            json.dumps(
                {"event": "numerics_dump_error", "error": repr(e)[:500]}
            ),
            file=sys.stderr,
            flush=True,
        )
    # The trip lands on every read surface: trace timeline instant,
    # telemetry counter (the nonfinite SLO rule fires on it at the
    # monitor's drain poll), structured JSONL event.
    trace.instant(
        "numerics_trip", metric=name, step=step, value=float(value)
    )
    telemetry.record_nonfinite_trip(name)
    log_event = getattr(logger, "event", None)
    if log_event is not None:
        try:
            log_event(
                "numerics_trip",
                metric=name,
                step=step,
                value=float(value),
                dump=dump_path,
                first_nonfinite=first,
            )
        except Exception:
            pass  # a broken sink must not mask the abort
    located = f" (first non-finite: {first})" if first else ""
    if dump_path:
        where = f"provenance dump at {dump_path}{located}"
    elif first:
        where = (
            f"first non-finite: {first} (pass --obs-dir or --log-dir to "
            "keep the full NUMERICS_DUMP.json)"
        )
    else:
        where = "provenance dump failed — see numerics_dump_error on stderr"
    raise FloatingPointError(
        f"non-finite {name} ({float(value)}) at or before step {step} "
        f"(checked {cadence}); {where}"
    )


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    total_steps: int = 1000
    log_every: int = 20
    checkpoint_every: int = 0  # 0 = no checkpointing
    eval_every: int = 0  # 0 = eval only at the end (if eval_fn given)
    checkpoint_dir: str | None = None
    resume: bool = True  # resume from latest checkpoint if present
    max_to_keep: int = 3
    # Profiling (SURVEY.md §5.1: jax.profiler replaces the reference's
    # nothing-beyond-TensorBoard): trace steps [profile_start_step,
    # profile_start_step + profile_steps) into profile_dir (per host).
    profile_dir: str | None = None
    profile_start_step: int = 10
    profile_steps: int = 5
    # Device-prefetch depth: a background thread pulls host batches and
    # enqueues their host→device transfers this many steps ahead, so step k
    # overlaps both batch k+1's DMA AND the host-side pipeline pull
    # (assembly, queue handoff).  2 = classic double buffering.  0 disables
    # the thread (transfer happens synchronously at each step — debugging).
    device_prefetch: int = 2
    # Run the mid-training eval hook in a background thread on a snapshotted
    # param copy instead of blocking the step stream (see _AsyncEvalRunner
    # for the safety contract; multi-process falls back to synchronous).
    # The FINAL eval stays synchronous either way.
    async_eval: bool = False
    # Numerics flight recorder (ISSUE 10, obs/numerics.py): fuse the
    # in-step grad/update health summary (global + per-group grad norms,
    # update/param ratio, non-finite count, cross-replica agreement) into
    # the compiled step.  Off (default) the compiled program and the
    # loop's record sites are unchanged (one bool check each).  The
    # NaN-provenance dump on a tripped finite-check is ALWAYS armed —
    # it only ever runs on the failure path.
    numerics: bool = False
    # Where NUMERICS_DUMP.json lands on a tripped finite-check; default =
    # the obs trace dir when tracing is on, else no file is written (the
    # abort message still carries the first-non-finite localization).
    numerics_dump_dir: str | None = None
    # Recorded in the provenance dump (reproduction context); train.py
    # passes --seed through.
    rng_seed: int | None = None
    # Recorded verbatim in every checkpoint manifest (utils/checkpoint.py).
    # train.py stores the data-order facts --resume-elastic re-derives the
    # stream position from (global batch size, data seed); anything a
    # future resume needs to validate against belongs here.
    ckpt_metadata: dict | None = None


def _device_batch(batch, mesh: Mesh | None, task) -> dict[str, Any]:
    """Host batch → the device-resident dict the train step consumes: the
    task's fields of it (train/task.py).

    Multi-host: each process holds its LOCAL shard of the global batch; the
    global jax.Array is assembled per process via
    ``make_array_from_process_local_data`` (the grain idiom).  Single-host:
    explicit ``device_put`` (sharded over the mesh when present) so the
    host→device DMA is enqueued HERE — which lets ``_prefetch_to_device``
    overlap batch N+1's transfer with step N's compute instead of paying it
    at dispatch (the reference relied on Keras' implicit feed; TPU input
    overlap must be explicit).
    """
    arrays = task.host_arrays(batch)
    if mesh is None:
        return {k: jax.device_put(v) for k, v in arrays.items()}
    if SPACE_AXIS in mesh.axis_names:
        # 2-D spatial mesh: images additionally shard H over `space`
        # (train.step.make_train_step_spatial).
        shardings = spatial_batch_shardings(mesh)
    else:
        s = batch_sharding(mesh)
        shardings = {k: s for k in arrays}
    if jax.process_count() == 1:
        return {
            k: jax.device_put(v, shardings[k]) for k, v in arrays.items()
        }
    return {
        k: jax.make_array_from_process_local_data(shardings[k], v)
        for k, v in arrays.items()
    }


def _prefetch_to_device(
    batches: Iterable, mesh: Mesh | None, depth: int, task
) -> Iterator[tuple[tuple[int, ...], int, np.ndarray, dict[str, Any]]]:
    """Yield (bucket, examples, ids, device_batch), ``depth`` ahead: the
    task's description of the host batch (train/task.py::describe) and its
    fields on the device.

    ``ids`` is the HOST copy of the batch's source ids — the numerics
    provenance dump records which examples fed a tripped step (the device
    batch deliberately carries no ids).

    Double-buffered device prefetch (the standard ``prefetch_to_device``
    idiom): a background thread pulls host batches and calls
    ``_device_batch`` — which enqueues the host→device DMA — up to ``depth``
    batches ahead of the training step, so step k's compute overlaps both
    batch k+1's transfer and the host side of producing it.  The thread /
    bounded-queue / stop / error skeleton is the shared ``prefetch_map``
    (data/prefetch.py) — the eval fast path (evaluate/detect.py) runs the
    same machinery with a different transfer.

    ``depth <= 0`` degrades to synchronous in-line transfer (debugging).
    The generator's ``close()`` stops the thread; exceptions from the
    pipeline (e.g. a crashed decode worker) are re-raised here.
    """
    return prefetch_map(
        batches,
        lambda batch: (
            *task.describe(batch),
            _device_batch(batch, mesh, task),
        ),
        depth=depth,
        thread_name="device-prefetch",
    )


class _AsyncEvalRunner:
    """Run the mid-training eval hook in a background thread on a
    snapshotted state, so the step stream keeps dispatching while the
    (host-heavy) eval runs: pipeline decode, detection post-processing and
    COCO scoring all happen off the loop's critical path, and the device
    interleaves eval detect programs between train steps instead of the
    host serializing a full eval pass into the step cadence.

    The "where safe" contract (LoopConfig.async_eval):

    - **Single-process only.**  A background thread issuing COLLECTIVES
      (the sharded eval's host all-gather, evaluate/detect.py) concurrently
      with the step stream can interleave differently across processes and
      deadlock the world; ``run_training`` falls back to synchronous eval
      (with a warning) when ``jax.process_count() > 1``.
    - **Snapshot, because the step donates.**  ``make_train_step`` donates
      its input state, so the thread cannot hold a reference into the live
      training state; the snapshot deep-copies params/batch_stats/step on
      device (async dispatch, enqueued before the next step's donation —
      the runtime orders the copy ahead of the donor) and DROPS opt_state:
      detection never reads it, and copying optimizer slots would double
      the snapshot memory for nothing.  Eval hooks used in async mode must
      therefore tolerate ``state.opt_state == ()`` (the in-tree hook does —
      the sharded branch already strips it).

    At most ONE eval is in flight: a new trigger joins the previous run
    first, so eval cadence provides natural backpressure instead of
    unbounded stacking.  Exceptions from the hook re-raise in the loop at
    the next drain/join; completed (step, metrics) pairs are logged from
    the LOOP thread (the JSONL logger is not locked for cross-thread
    appends).
    """

    def __init__(self, eval_fn, logger) -> None:
        self._eval_fn = eval_fn
        self._logger = logger
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None
        self._done: list[tuple[int, dict]] = []
        self._lock = threading.Lock()

    def launch(self, state, step: int) -> None:
        import jax.numpy as jnp

        self.join()  # one in flight; also surfaces a prior failure
        # Drop opt_state AND comm_state (ISSUE 13 EF residuals): eval
        # reads neither, and the residuals are data-axis-sharded.
        snapshot = jax.tree.map(
            jnp.copy, state.replace(opt_state=(), comm_state=())
        )

        def run() -> None:
            # Registered but immediately idle: a mid-training eval is
            # minutes of legitimate silence, and its LIVENESS is witnessed
            # by the components the eval itself spins up (eval-device-
            # prefetch, eval-consumer, the val pipeline's producer) — a
            # wedged eval shows up as THEIR stall, correctly attributed.
            hb = watchdog.register("async-eval")
            hb.idle()
            try:
                with trace.span("async_eval", step=step):
                    metrics = self._eval_fn(snapshot)
                with self._lock:
                    self._done.append((step, metrics))
            except BaseException as exc:  # surfaced at the next drain/join
                self._error = exc
            finally:
                hb.close()

        # watchdog: registers in run() at thread start.
        self._thread = threading.Thread(
            target=run, daemon=True, name="async-eval"
        )
        self._thread.start()

    def drain(self) -> None:
        """Log completed evals (loop thread); re-raise a failed one."""
        if self._error is not None:
            error, self._error = self._error, None
            raise RuntimeError("async eval hook failed") from error
        with self._lock:
            done, self._done = self._done, []
        for step, metrics in done:
            self._logger.log(step, metrics, prefix="eval")

    def join(self) -> None:
        """Wait for the in-flight eval (if any), then drain."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self.drain()

    def finalize_on_error(self) -> None:
        """Unwind path: the loop is already propagating another exception.
        Join the in-flight eval (so its pipelines/threads are reclaimed
        before the process state is inspected) and log what completed, but
        WARN instead of raising — a failed eval must not mask the original
        error."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        try:
            self.drain()
        except Exception as exc:
            warnings.warn(f"async eval failed during loop unwind: {exc!r}")


# The step functions the last ``run_training`` call built, by the task's
# bucket ((H, W) for detection), each with the abstract arguments it was
# first called on.  What ``compiled_step`` lowers again; no device buffer of
# the state or a batch is kept (the state is donated to the step).  The
# functions, and so their loaded executables, live until the next call
# replaces them.
_built_steps: dict[tuple[int, ...], tuple[Callable, tuple]] = {}
# Builds of a train step so far in this process, by bucket name
# (``compile_train_step``'s ``call=``).
_step_builds: dict[str, int] = {}


def _bucket_name(bucket: tuple[int, ...]) -> str:
    return "x".join(str(n) for n in bucket)


def _abstract(tree):
    """Shapes, dtypes and (where committed) shardings of a tree of arrays."""

    def leaf(x):
        if not isinstance(x, jax.Array):  # restored state: host numpy
            return jax.ShapeDtypeStruct(np.shape(x), np.result_type(x))
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None,
        )

    return jax.tree.map(leaf, tree)


def compiled_step(hw: tuple[int, ...] | None = None):
    """The ``jax.stages.Compiled`` of the train step the last
    ``run_training`` call built for bucket ``hw`` (default: the bucket it
    built last): for its ``cost_analysis()``, its ``as_text()``
    (``train/step.py::scope_table``), its ``memory_analysis()``.

    Lowers the SAME function on the abstract arguments of its first call
    and compiles.  Once the step has run, jit's own caches answer both with
    the executable the loop ran (no request reaches the compiler or the
    persistent cache; tests/unit/test_step_scopes.py counts them), so its
    instruction names are the ones a device trace shows: call it AFTER the
    step's first execution, never before.  Raises ``LookupError`` when no
    step was built, or none for ``hw``."""
    if hw is None and _built_steps:
        hw = next(reversed(_built_steps))
    if hw not in _built_steps:
        raise LookupError(f"run_training has built no train step for bucket {hw}")
    step_fn, args = _built_steps[hw]
    return step_fn.lower(*args).compile()


def _record_step_cost(hw, batch: int) -> None:
    """The ``cost_analysis`` trace instant (the perf doctor's MFU estimate
    reads it, obs/analyze): XLA-counted FLOPs of the compiled step for
    bucket ``hw``, after its first execution.  Nothing where the backend
    offers no cost analysis — the report then carries ``mfu: null``
    instead of a guess."""
    try:
        cost = compiled_step(hw).cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else None
        flops = float(cost.get("flops", 0.0)) if cost else 0.0
    except Exception:
        return
    if flops > 0:
        trace.instant(
            "cost_analysis", target="train_step", bucket=_bucket_name(hw),
            flops=flops, batch=batch,
        )


def _record_run_meta(task, model, bucket) -> None:
    """Run metadata INTO the trace (the perf doctor resolves device peak
    TFLOP/s and process topology from artifacts alone — the events JSONL
    may not exist for this run), with what the task says of the step it is
    about to build of ``model`` for ``bucket`` (train/task.py::run_meta)."""
    try:
        trace.instant(
            "run_meta",
            device_kind=jax.devices()[0].device_kind,
            local_device_count=jax.local_device_count(),
            process_count=jax.process_count(),
            **task.run_meta(model, bucket),
        )
    except Exception:
        pass  # metadata must never block training bring-up


def _profile_options():
    """``--profile-dir``'s profiler session: device operations and host
    annotations (this program's ``rn.*`` spans among them), no Python call
    tracing.  JAX's defaults wrote 850 MB for 40 flagship steps and took
    minutes to stop (PERF.md, PR 22).  The HLO proto stays on, so XProf
    shows the step's scopes (train/step.py::STEP_SCOPES) itself."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    return options


def _compile_barrier(step_fn, state, device_arrays, hw) -> None:
    """Compile the step, then barrier at the COORDINATION SERVICE before
    its first execution on multi-process runs.

    XLA:CPU's Gloo collectives carry a hardcoded ~30 s receive timeout,
    and TPU collectives have finite timeouts too — while a cold step
    compile takes minutes.  Without this, the first process to finish
    compiling enters the step's collectives and times out waiting for
    peers still compiling (observed as deterministic-looking
    Gloo ReduceScatter failures in the 2-process ZeRO world, round 3).
    The coordination-service barrier (gRPC, 10 min budget) holds everyone
    until every process has COMPILED; execution then starts aligned.

    Error policy: a genuine compile failure PROPAGATES (the step would
    fail at dispatch anyway, and a swallowed compile error would defeat
    the barrier — the healthy peers would time out in collectives while
    this process died later with a confusing secondary error).  Only the
    genuinely optional pieces degrade to a skip: a step wrapper without
    the AOT ``lower`` surface, or no distributed client (world brought
    up outside ``jax.distributed.initialize``).

    Bucket-order assumption: the barrier name is derived from the (H, W)
    bucket, so every process must reach new buckets in the same order.
    That holds by construction here — the global batch is assembled from
    aligned per-process shards of one global stream, so every process
    sees the same bucket at the same step index.  A custom per-process
    pipeline that broke this would park processes at differently-named
    barriers until the 10-minute budget expires (a loud, attributable
    failure rather than a silent data skew).
    """
    if jax.process_count() <= 1:
        return
    lower = getattr(step_fn, "lower", None)
    if lower is None:
        return  # no AOT surface: first dispatch compiles (and may skew)
    lower(state, device_arrays).compile()  # compile errors propagate
    from jax._src import distributed  # private: no public barrier API

    client = distributed.global_state.client
    if client is None:
        return  # no coordination service (external world bring-up)
    client.wait_at_barrier(f"train_step_compiled_{_bucket_name(hw)}", 600_000)


def _place_state(state: TrainState, mesh: Mesh, shard_weight_update: bool) -> TrainState:
    """Replicate ``state`` over ``mesh`` (restored arrays land committed to
    a single device, which conflicts with the shard_map'd step).  In
    weight-update-sharded mode the opt_state leaves keep their 1/N layout
    on the data axis instead (parallel/zero.py storage format)."""

    def _place_comm_state(comm_state):
        # Comm EF residuals (ISSUE 13) keep their 1/N data-axis
        # layout, exactly like ZeRO optimizer state.
        from jax.sharding import NamedSharding

        from batchai_retinanet_horovod_coco_tpu.comm.compress import (
            state_partition_specs,
        )

        return jax.tree.map(
            lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
            comm_state,
            state_partition_specs(comm_state),
        )

    if shard_weight_update:
        from jax.sharding import NamedSharding

        from batchai_retinanet_horovod_coco_tpu.parallel.zero import (
            opt_state_partition_specs,
        )

        rep = replicated_sharding(mesh)
        opt_state = jax.tree.map(
            lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
            state.opt_state,
            opt_state_partition_specs(state.opt_state),
        )
        state = state.replace(
            step=jax.device_put(state.step, rep),
            params=jax.device_put(state.params, rep),
            batch_stats=jax.device_put(state.batch_stats, rep),
            opt_state=opt_state,
            comm_state=_place_comm_state(state.comm_state),
        )
    elif getattr(state, "comm_state", ()):
        rep = replicated_sharding(mesh)
        state = state.replace(
            step=jax.device_put(state.step, rep),
            params=jax.device_put(state.params, rep),
            batch_stats=jax.device_put(state.batch_stats, rep),
            opt_state=jax.device_put(state.opt_state, rep),
            comm_state=_place_comm_state(state.comm_state),
        )
    else:
        state = jax.device_put(state, replicated_sharding(mesh))
    return state


def run_training(
    model,
    state: TrainState,
    batches: Iterable,
    num_classes: int | None,
    config: LoopConfig,
    mesh: Mesh | None = None,
    loss_config: losses_lib.LossConfig = losses_lib.LossConfig(),
    matching_config: matching_lib.MatchingConfig = matching_lib.MatchingConfig(),
    anchor_config=None,
    schedule: Callable[[int], float] | None = None,
    eval_fn: Callable[[TrainState], dict[str, float]] | None = None,
    logger: EventSink | None = None,
    shard_weight_update: bool = False,
    comm=None,
    topology=None,
    allow_data_axis_divergence: bool = False,
    task=None,
) -> TrainState:
    """Run ``config.total_steps`` of SPMD training; returns the final state.

    ``task`` (train/task.py) says what the batches are and what loss they
    train; unset it is detection from ``num_classes``, ``loss_config``,
    ``matching_config`` and ``anchor_config``.  One train step is compiled
    per bucket of the task seen in the stream ((H, W) for detection).

    ``eval_fn(state) -> metrics`` is the CocoEval-callback equivalent, called
    every ``eval_every`` steps and at the end.

    ``comm`` (a ``comm.CommConfig``, ISSUE 13) selects the gradient-
    communication policy — bucketed int8/bf16 compression with error
    feedback, optional backward overlap; composes with
    ``shard_weight_update`` (the compression moves to the ZeRO update
    gather).
    ``topology`` (a ``parallel.mesh.CommTopology``, ISSUE 16) makes the
    comm collective hierarchical — exact within each ICI slice,
    compressed only on the cross-slice DCN hop (train/step.py).

    A 2-D mesh carrying a ``space`` axis selects the spatially partitioned
    step (image-H sharding; train/step.py::make_train_step_spatial) —
    exclusive with the ZeRO and comm-compression flavors.
    """
    if task is None:
        task = DetectionTask(num_classes, loss_config, matching_config, anchor_config)
    spatial = mesh is not None and SPACE_AXIS in mesh.axis_names
    if spatial and not isinstance(task, DetectionTask):
        raise ValueError(f"spatial partitioning shards images: not the {task.name} task")
    comm_on = comm is not None and getattr(comm, "enabled", False)
    if spatial and (shard_weight_update or comm_on):
        raise ValueError(
            "spatial partitioning is exclusive with --shard-weight-update "
            "and --comm-compress"
        )
    logger = logger or EventSink(log_dir=None)
    ckpt = None
    if config.checkpoint_every and config.checkpoint_dir:
        ckpt = CheckpointManager(
            config.checkpoint_dir,
            max_to_keep=config.max_to_keep,
            save_interval_steps=config.checkpoint_every,
            metadata=config.ckpt_metadata,
            sink=logger,
        )
        if config.resume and ckpt.latest_step() is not None:
            t_restore = monotonic_s()
            template = state
            try:
                with trace.phase("ckpt_restore"):
                    state = ckpt.restore(template)
            except Exception as e:
                raise RuntimeError(
                    f"restoring {config.checkpoint_dir} failed (root cause "
                    "in the chained traceback). Optimizer-state layouts "
                    "reshard automatically across world sizes and between "
                    "--shard-weight-update and replicated mode "
                    "(utils/checkpoint.py), so a shape mismatch here means "
                    "a DIFFERENT model/optimizer was checkpointed; "
                    "otherwise every checkpoint in the directory is torn — "
                    "see ckpt_torn_skipped on stderr, or start fresh with "
                    "--no-resume."
                ) from e
            print(f"resumed from step {int(state.step)}", flush=True)
            restore_s = monotonic_s() - t_restore
            log_event = getattr(logger, "event", None)
            if log_event is not None:
                log_event(
                    "ckpt_restored",
                    step=int(state.step),
                    restore_s=round(restore_s, 4),
                )
            if jax.process_count() == 1:
                # Restored leaves are HOST numpy.  Materialize jax-OWNED
                # device buffers via a compiled copy (jnp.copy), never a
                # bare device_put: XLA:CPU's device_put is ZERO-COPY for
                # numpy inputs, the train step DONATES its input state,
                # and donating a numpy-aliased buffer hands numpy-owned
                # memory to XLA's allocator — observed as glibc heap
                # corruption ("corrupted double-linked list") at the
                # first post-resume step.  The mesh replication below
                # then proceeds from committed device arrays, exactly as
                # it always has.  Multi-host keeps host numpy: every
                # process restored identical values and the replication
                # block's global device_put wants process-local host
                # data (TPU puts always copy; the alias hazard is
                # CPU-backend-only).
                # The template's buffer goes as each restored leaf arrives:
                # the caller's state is consumed here as the first
                # (donating) step would consume it, and a state that fills
                # most of the chip (9.3 GB of parameters and Adam slots of
                # 16: PERF.md section 6, PR 26) cannot be restored beside
                # its own template.
                import jax.numpy as jnp

                def _replace(old, new):
                    if isinstance(old, jax.Array) and not old.is_deleted():
                        old.delete()
                    return jnp.copy(new)

                state = jax.tree.map(_replace, template, state)

    if mesh is not None:
        # A phase of the set-up record: the host's time to ISSUE the
        # placement (a device_put per leaf); the copies themselves finish
        # under whatever runs next.
        with trace.phase("place_state", devices=int(mesh.devices.size)):
            state = _place_state(state, mesh, shard_weight_update)

    step_fns: dict[tuple[int, ...], Callable] = {}
    _built_steps.clear()
    start_step = int(state.step)
    last_saved: int | None = None
    # Clamp the profile window into the steps this run will actually take
    # (otherwise short runs would never produce a trace).
    prof_start = min(
        max(config.profile_start_step, start_step + 1),
        max(start_step + 1, config.total_steps - config.profile_steps + 1),
    )
    prof_end = min(config.total_steps, prof_start + config.profile_steps - 1)
    window_t0 = monotonic_s()
    window_images = 0
    window_data_wait = 0.0  # host time blocked on the input pipeline
    window_steps = 0
    metrics = None
    eval_runner = None
    if config.async_eval and eval_fn is not None:
        if jax.process_count() > 1:
            warnings.warn(
                "async_eval requested in a multi-process world; falling "
                "back to synchronous eval (a background thread issuing the "
                "eval all-gather concurrently with step collectives can "
                "deadlock — see _AsyncEvalRunner)"
            )
        else:
            eval_runner = _AsyncEvalRunner(eval_fn, logger)
    # Numerics flight recorder: the in-step summary gate (compile-time —
    # the disabled step's program is unchanged) plus the always-armed
    # provenance context for a tripped finite-check.
    numerics_config = NumericsConfig(enabled=config.numerics)

    def start_profile_if_due(step: int) -> None:
        if config.profile_dir and step == prof_start:
            jax.profiler.start_trace(
                config.profile_dir, profiler_options=_profile_options()
            )

    it = _prefetch_to_device(batches, mesh, config.device_prefetch, task)
    # The loop's own heartbeat: one beat per step.  Long legitimate gaps
    # (sync eval, final epilogue) are bracketed with idle() so only a
    # genuinely wedged step stream — or the data stall it is blocked on —
    # trips the watchdog.  The details cell is HOST-side: the watchdog
    # thread must never touch ``state.step`` (a possibly-donated device
    # array).
    last_step = [start_step]
    loop_hb = watchdog.register(
        "train-loop", details=lambda: {"step": last_step[0]}
    )

    try:
        for step in range(start_step + 1, config.total_steps + 1):
            if eval_runner is not None:
                eval_runner.drain()  # log finished evals; surface failures
            loop_hb.beat()
            last_step[0] = step
            t_data = monotonic_s()
            with trace.span("data_wait"):
                hw, examples, image_ids, device_arrays = next(it)
            window_data_wait += monotonic_s() - t_data
            window_steps += 1
            step_fn = step_fns.get(hw)
            new_step = step_fn is None
            if new_step:
                # AOT point: build + (multi-process) compile-and-barrier +
                # the new step's FIRST call, where a one-process run traces,
                # lowers and loads or compiles it.  The phase/event turn
                # each bucket's one-time multi-minute gap into an attributed
                # compile, not an apparent stall — and the heartbeat goes
                # idle for the same reason (a cold flagship compile is
                # minutes, far past any stall budget).
                loop_hb.idle()
                if not step_fns and trace.enabled():
                    _record_run_meta(task, model, hw)
                bucket = _bucket_name(hw)
                # The n-th build of this bucket in this process: a caller
                # that runs the loop twice (warm-up, then the real call)
                # sees what the second build still costs.
                call = _step_builds[bucket] = _step_builds.get(bucket, 0) + 1
                with trace.phase(
                    "compile_train_step", bucket=bucket, call=call
                ) as built:
                    if spatial:
                        step_fn = step_fns[hw] = make_train_step_spatial(
                            model,
                            hw,
                            num_classes,
                            mesh=mesh,
                            loss_config=loss_config,
                            matching_config=matching_config,
                            anchor_config=anchor_config,
                            allow_data_axis_divergence=allow_data_axis_divergence,
                            numerics=numerics_config,
                        )
                    else:
                        step_fn = step_fns[hw] = make_train_step(
                            model,
                            hw,
                            num_classes,
                            mesh=mesh,
                            loss_config=loss_config,
                            matching_config=matching_config,
                            anchor_config=anchor_config,
                            shard_weight_update=shard_weight_update,
                            comm=comm,
                            topology=topology,
                            numerics=numerics_config,
                            task=task,
                        )
                    # No process may enter the step's collectives while a
                    # peer is still compiling (collective timeouts <<
                    # compile times).
                    _compile_barrier(step_fn, state, device_arrays, hw)
                    _built_steps[hw] = (
                        step_fn, _abstract((state, device_arrays))
                    )
                    start_profile_if_due(step)
                    state, metrics = step_fn(state, device_arrays)
                loop_hb.beat()
                # Live-telemetry record site (one bool check while off):
                # the status server's train_compiles_total/last_compile.
                telemetry.record_compile(bucket, built.dur)
                # Duck-typed: tests pass bare .log-only logger fakes.
                log_event = getattr(logger, "event", None)
                if log_event is not None:
                    log_event(
                        "compile",
                        target="train_step",
                        bucket=bucket,
                        step=step,
                        build_s=round(built.dur, 3),
                    )
            else:
                start_profile_if_due(step)
                with trace.span("step"):
                    state, metrics = step_fn(state, device_arrays)
            if new_step and trace.enabled():
                # Obs runs record the step's XLA-counted FLOPs so
                # PERF_REPORT.json can carry an MFU estimate: from the
                # executable the call above just compiled or loaded,
                # which the compile cache hands back.
                _record_step_cost(hw, int(examples))
            if config.profile_dir and step == prof_end:
                jax.block_until_ready(metrics)
                jax.profiler.stop_trace()
            # Global batch size = local batch × process_count (each process
            # feeds its shard of the global batch).
            window_images += examples * (
                jax.process_count() if mesh is not None else 1
            )

            # ``step`` is tracked host-side (state.step mirrors it) so the loop
            # never forces a per-step device sync (that would drain the
            # dispatch queue and idle the device between steps); the
            # finiteness sanitizer therefore runs at a bounded cadence — every
            # log window, every _FINITE_CHECK_EVERY steps when log_every=0, and
            # unconditionally before any checkpoint save (a NaN-poisoned state
            # must never reach disk: auto-resume would restore the poison and
            # make recovery impossible without --no-resume).
            is_log = (
                config.log_every and step % config.log_every == 0
            ) or step == config.total_steps
            will_save = ckpt is not None and ckpt.should_save(step)
            check_every = config.log_every or _FINITE_CHECK_EVERY
            cadence = (
                f"every {check_every} steps and before each checkpoint save"
            )
            # Both check sites — the bounded cadence check and the
            # pre-save poisoned-state gate (``will_save``) — go through
            # ONE finite helper (obs/numerics.first_nonfinite_scalar) and
            # one abort path (provenance dump + raise); test_numerics
            # pins both.
            if not is_log and (will_save or step % check_every == 0):
                sentinels = {
                    name: jax.device_get(metrics[name])
                    for name in _SENTINEL_METRICS
                    if name in metrics
                }
                hit = numerics_lib.first_nonfinite_scalar(sentinels)
                if hit is not None:
                    _abort_nonfinite(
                        hit[0], hit[1], step, cadence,
                        model=model, state=state,
                        device_arrays=device_arrays, image_ids=image_ids,
                        metrics=metrics, rng_seed=config.rng_seed,
                        dump_dir=config.numerics_dump_dir, logger=logger,
                    )

            if is_log:
                with trace.span("metrics_fetch"):
                    scalars = {
                        k: v for k, v in jax.device_get(metrics).items()
                    }
                hit = numerics_lib.first_nonfinite_scalar(
                    {k: scalars[k] for k in _SENTINEL_METRICS if k in scalars}
                )
                if hit is not None:
                    _abort_nonfinite(
                        hit[0], hit[1], step, cadence,
                        model=model, state=state,
                        device_arrays=device_arrays, image_ids=image_ids,
                        metrics=metrics, rng_seed=config.rng_seed,
                        dump_dir=config.numerics_dump_dir, logger=logger,
                    )
                dt = monotonic_s() - window_t0
                scalars["images_per_sec"] = window_images / max(dt, 1e-9)
                # Step-time breakdown (SURVEY.md §5.5): how much of the step the
                # host spent BLOCKED on the input pipeline — the classic
                # detection scaling-efficiency killer (SURVEY.md §7.3 part 6).
                scalars["step_time_ms"] = dt / max(window_steps, 1) * 1e3
                scalars["data_wait_ms"] = (
                    window_data_wait / max(window_steps, 1) * 1e3
                )
                # Cumulative gt boxes dropped by max_gt padding (pipeline
                # counter) — silent truncation poisons targets, so it is a
                # first-class metric whenever it is nonzero.
                pipe_stats = getattr(batches, "stats", None)
                if pipe_stats is not None and pipe_stats.truncated_boxes:
                    scalars["truncated_gt_boxes"] = pipe_stats.truncated_boxes
                if schedule is not None:
                    scalars["lr"] = float(schedule(step - 1))
                    scale = optim.plateau_scale(state.opt_state)
                    if scale is not None:
                        scalars["lr"] *= scale  # data-driven ReduceLROnPlateau
                logger.log(step, scalars)
                # Live-telemetry record site (one bool check while off):
                # step rate / step time / data-wait fraction for the
                # --obs-port status server and the SLO monitor's rules.
                telemetry.record_train_window(
                    step=step,
                    images_per_s=scalars["images_per_sec"],
                    step_time_ms=scalars["step_time_ms"],
                    data_wait_ms=scalars["data_wait_ms"],
                )
                # Numerics record sites (ISSUE 10; each one bool check
                # while its plane is off): the grad_norm/update_ratio/
                # nonfinite gauges feed the SLO monitor's built-in
                # nonfinite + grad-norm-spike rules whenever telemetry
                # is live; the dedicated structured JSONL record (the
                # perf doctor's numerics section) exists only when the
                # in-step summary is on.
                telemetry.record_numerics(
                    grad_norm=scalars.get(numerics_lib.GRAD_NORM),
                    update_ratio=scalars.get(numerics_lib.UPDATE_RATIO),
                    nonfinite=scalars.get(numerics_lib.NONFINITE),
                    replica_agreement=scalars.get(
                        numerics_lib.REPLICA_AGREEMENT
                    ),
                )
                # Comm/EF health record site (ISSUE 13; one bool check
                # while telemetry is off, absent keys skipped): feeds
                # the train_ef_residual/saturation gauges the always-
                # armed ef_residual_spike SLO rule watches, plus the
                # cumulative bytes-on-wire counter.
                telemetry.record_comm(
                    ef_residual=scalars.get(numerics_lib.EF_RESIDUAL),
                    ef_saturation=scalars.get(numerics_lib.EF_SATURATION),
                    compressed_bytes=scalars.get(numerics_lib.COMM_BYTES),
                    # Per-hop plane (ISSUE 16): present only on
                    # hierarchical runs — ICI/DCN byte counters plus the
                    # DCN-labeled residual gauge the per-hop
                    # ef_residual_spike rule watches.
                    ici_bytes=scalars.get(numerics_lib.COMM_ICI_BYTES),
                    dcn_bytes=scalars.get(numerics_lib.COMM_DCN_BYTES),
                    ef_residual_dcn=scalars.get(
                        numerics_lib.EF_RESIDUAL_DCN
                    ),
                    steps=window_steps,
                )
                if config.numerics:
                    num_keys = numerics_lib.numerics_metric_keys(scalars)
                    log_event = getattr(logger, "event", None)
                    if log_event is not None and num_keys:
                        log_event(
                            "numerics",
                            step=step,
                            **{k: float(scalars[k]) for k in num_keys},
                        )
                if trace.enabled():
                    # Device HBM occupancy as Chrome counter tracks, once
                    # per log window (memory_stats() is a host call; CPU
                    # backends report nothing and this is a no-op).
                    for name, value in device_memory_stats():
                        trace.counter(name, value)
                window_t0 = monotonic_s()
                window_images = 0
                window_data_wait = 0.0
                window_steps = 0

            if will_save and ckpt.save(state, step=step):
                last_saved = step

            if (
                eval_fn is not None
                and config.eval_every
                and step % config.eval_every == 0
                and step < config.total_steps
            ):
                if eval_runner is not None:
                    # Usually non-blocking: the hook runs on a snapshotted
                    # copy while the step stream continues.  No window
                    # reset — the steps keep flowing (the eval's device
                    # work shows up honestly as slightly slower steps, not
                    # as a gap).  BUT launch() first joins a still-running
                    # previous eval (one in flight max), which can block
                    # for minutes when eval_every < eval duration — idle
                    # the loop heartbeat across it, as the sync branch
                    # below does.
                    loop_hb.idle()
                    eval_runner.launch(state, step)
                    loop_hb.beat()
                else:
                    # Synchronous eval: minutes of legitimate step-stream
                    # silence — idle the loop heartbeat (the eval's own
                    # components carry liveness) and re-arm after.
                    loop_hb.idle()
                    with trace.span("eval", step=step):
                        eval_metrics = eval_fn(state)
                    loop_hb.beat()
                    logger.log(step, eval_metrics, prefix="eval")
                    # Eval time must not pollute the next window's
                    # step-time metrics.
                    window_t0 = monotonic_s()
                    window_images = 0
                    window_data_wait = 0.0
                    window_steps = 0

    except BaseException:
        # Exception exit: reap the in-flight async eval during unwind (its
        # error/metrics are warned/logged, never raised — they must not
        # mask the original exception).  An explicit except, not a
        # sys.exc_info() probe in the finally — exc_info is thread-wide
        # and would misfire when run_training is itself called inside a
        # caller's except block.  The normal path joins below, where eval
        # failures DO raise.
        if eval_runner is not None:
            eval_runner.finalize_on_error()
        if ckpt is not None:
            # Quiesce the async writer BEFORE the exception escapes: an
            # --auto-resume caller re-enters with a NEW manager on the
            # same directory, and an abandoned in-flight write racing it
            # could gc the new writer's tmp dir or publish a pre-abort
            # state after the heal chose its restore point.  close()
            # joins the in-flight save (a healthy, pre-abort checkpoint
            # — letting it land is exactly right); its own failure is
            # warned, never raised — it must not mask the original
            # exception.
            try:
                ckpt.close()
            except Exception as ckpt_exc:
                warnings.warn(
                    "checkpoint writer close failed during loop unwind: "
                    f"{ckpt_exc!r}"
                )
        raise
    finally:
        # Stop the prefetch thread deterministically (even when the
        # loop exits via an exception) before eval/checkpoint epilogue.
        it.close()
        # The step stream is over; the epilogue (final eval, checkpoint
        # flush) has its own components/timeouts.
        loop_hb.close()

    final_step = max(start_step, config.total_steps)
    if eval_runner is not None:
        # The final eval below is synchronous; finish (and log, in step
        # order) any still-running mid-run eval first.
        eval_runner.join()
    if eval_fn is not None:
        with trace.span("final_eval", step=final_step):
            final_metrics = eval_fn(state)
        logger.log(final_step, final_metrics, prefix="eval")
    if ckpt is not None:
        if last_saved != final_step:
            ckpt.save(state, step=final_step, force=True)
        ckpt.close()
    return state
