"""Crash-safe async checkpoint/resume, world-size-elastic (ISSUE 11).

Reference behavior being replaced (SURVEY.md §5.4): Keras ``ModelCheckpoint``
on rank 0 wrote one full-model ``.h5`` per epoch WITHOUT optimizer state, so
resume restarted the optimizer.  Rounds 1-10 used orbax for the full train
state; this module replaces it with a native writer because orbax's async
finalize thread (cross-thread asyncio wakeups + grpc) segfaulted under
sandboxed kernels, forcing tests — and any similar production host — onto
the synchronous path, and because its storage format pinned a ZeRO-sharded
optimizer state to the world size that wrote it.

**Format** — one directory per checkpoint, scanned (never indexed):

    <dir>/ckpt-<step>/
        leaf_00000.npy ...    # tree leaves, keypath order
        manifest.json         # committed LAST: keypaths, shapes, dtypes,
                              # sizes, crc32s, zero_world_size, metadata

**Crash-safety protocol** (the whole point): leaves are written into
``<dir>/.tmp-<step>-<pid>`` and fsync'd; the manifest is written (and
fsync'd) into the tmp dir LAST; one atomic ``os.rename`` publishes the
directory; the parent directory is fsync'd after.  A ``SIGKILL`` at ANY
instant therefore leaves either the previous complete checkpoint or the
new one — a dir without a manifest, or whose manifest disagrees with its
files, is torn by definition and the restore scan skips it (one
structured ``ckpt_torn_skipped`` stderr line, then the next-newest valid
checkpoint).  ``scripts/chaos.py`` kills a real training subprocess at
every phase of this protocol and asserts exactly that.

**Async contract** — ``save()`` snapshots device→host synchronously in
the caller's thread (the training loop's step serialization is the step
lock: the snapshot sees exactly the state at the save step) and hands the
host tree to ONE long-lived background writer thread, so the disk write
overlaps subsequent train steps.  Bounded one-behind: a new save first
joins the previous in-flight write, so at most one checkpoint of host
memory is ever pinned and saves can never stack.  The writer is
watchdog-registered, spans its work (``ckpt_write``), feeds the telemetry
gauges (``ckpt_save_s`` / ``ckpt_inflight`` / ``ckpt_last_success_age_s``,
obs/telemetry.py — the staleness SLO rule watches the age), and carries
the shm-pipeline error contract: a writer crash is announced on stderr
and re-raised in the training loop at the next ``save()``/``wait()``/
``close()``.  ``RETINANET_ASYNC_CKPT=0`` remains as an escape hatch
selecting the synchronous in-caller-thread path (same protocol, no
thread).

**World-size elasticity** — the pytree structure of a ZeRO-sharded
optimizer state equals the replicated one (parallel/zero.py); only leaf
shapes differ, and the flat layout's padding is zeros.  Leaves are saved
in whatever layout the run used, keyed by tree path, and ``restore()``
re-lays each optimizer leaf into the TEMPLATE's layout via
``zero.reshard_flat_leaf`` — so a checkpoint written at world size N
restores at world size M (N ≠ M in either direction, including M = 1:
replicated single-host recovery of a pod checkpoint).  Params/batch
stats/step require exact shape+dtype (a mismatch there is a different
model, never a resharding problem).

Multi-host: every process calls ``save()`` (non-addressable sharded
leaves are gathered collectively), process 0 writes.  Deliberate trade:
the gather costs one all-gather of the ZeRO optimizer state per save —
at pod save cadences (O(1000) steps) that is noise, and it is what buys
the world-free on-disk layout; per-process shard files (restore already
re-lays arbitrary flat layouts) are the future optimization if a profile
ever blames checkpoint-interval network.
"""

from __future__ import annotations

import dataclasses
import json
import os
import queue
import re
import shutil
import signal
import sys
import threading
import zlib
from typing import Any, Callable, Mapping

import jax
import numpy as np

from batchai_retinanet_horovod_coco_tpu.obs import telemetry, trace, watchdog
from batchai_retinanet_horovod_coco_tpu.obs.trace import monotonic_s
from batchai_retinanet_horovod_coco_tpu.parallel.zero import reshard_flat_leaf
from batchai_retinanet_horovod_coco_tpu.train.state import TrainState
from batchai_retinanet_horovod_coco_tpu.utils.atomicio import (
    atomic_write_json,
    fsync_dir,
)

FORMAT = "retinanet-ckpt"
FORMAT_VERSION = 1

_STEP_DIR_RE = re.compile(r"^ckpt-(\d+)$")
_TMP_PREFIX = ".tmp-"

# Production default: async (the write overlaps train steps).
# RETINANET_ASYNC_CKPT=0 selects the synchronous path — kept as an escape
# hatch for debugging; the native writer is plain stdlib threading (no
# asyncio, no grpc), so the orbax finalize-segfault class that forced the
# test env onto this path is gone and tests run async like production.


def _async_default() -> bool:
    return os.environ.get("RETINANET_ASYNC_CKPT", "1").lower() not in (
        "0", "false",
    )


# ---------------------------------------------------------------------------
# Fault-injection hooks (scripts/chaos.py)
# ---------------------------------------------------------------------------

# RETINANET_CHAOS_KILL="<phase>@<n>": SIGKILL this process at the n-th
# (1-based) crossing of the named save phase.  Phases, in protocol order:
# snapshot, tmp_write, manifest_commit, rename, finalize.  Counters are
# per-process; the chaos harness schedules one (phase, n) per subprocess
# so every kill lands at a known protocol point.
_chaos_counts: dict[str, int] = {}


def _chaos_point(phase: str) -> None:
    spec = os.environ.get("RETINANET_CHAOS_KILL")
    if not spec:
        return
    name, _, n = spec.partition("@")
    if name != phase:
        return
    _chaos_counts[phase] = _chaos_counts.get(phase, 0) + 1
    if _chaos_counts[phase] == int(n or 1):
        print(
            json.dumps({"event": "chaos_kill", "phase": phase,
                        "occurrence": _chaos_counts[phase]}),
            file=sys.stderr, flush=True,
        )
        sys.stderr.flush()
        os.kill(os.getpid(), signal.SIGKILL)


# ---------------------------------------------------------------------------
# Tree <-> flat leaves
# ---------------------------------------------------------------------------


def _saveable(state: TrainState) -> dict[str, Any]:
    """The pytree that goes to disk (drops the static optax transform).

    ``comm_state`` (ISSUE 13: gradient-compression EF residuals) rides
    along; it is ``()`` — zero leaves, manifest unchanged — for every
    run without compression, so pre-ISSUE-13 checkpoints and
    uncompressed runs keep the exact same on-disk leaf set."""
    return {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "comm_state": getattr(state, "comm_state", ()),
    }


def _flatten_with_paths(tree: Any) -> list[tuple[str, Any]]:
    """(stable keypath string, leaf) pairs — the on-disk leaf identity.

    The keypath strings are ``jax.tree_util.keystr`` output; a sharded and
    a replicated opt_state flatten to the SAME paths (same treedef), which
    is what lets restore re-lay layouts leaf-by-leaf.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _detect_zero_world(opt_state: Any) -> int | None:
    """World size of a ZeRO-sharded opt_state (None = replicated layout),
    read off the leaves' NamedSharding specs (the storage-format rule,
    parallel/zero.py::opt_state_partition_specs)."""
    for leaf in jax.tree_util.tree_leaves(opt_state):
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        if spec is not None and any(axis is not None for axis in spec):
            return int(sharding.mesh.size)
    return None


_gather_jits: dict[Any, Callable] = {}


def _replicate_global(x: Any) -> Any:
    """Reshard one globally-sharded array to fully-replicated via a jit
    identity (compiles to one all-gather; every process participates).
    One jit per mesh — jax caches the per-shape executables under it."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = x.sharding.mesh
    fn = _gather_jits.get(mesh)
    if fn is None:
        fn = _gather_jits[mesh] = jax.jit(
            lambda a: a, out_shardings=NamedSharding(mesh, PartitionSpec())
        )
    return fn(x)


def _host_leaf(x: Any) -> np.ndarray:
    """One leaf device→host, as an OWNED copy.  Non-fully-addressable
    arrays (cross-host ZeRO shards) are gathered collectively — every
    process must be inside ``save()`` when this runs (they are: save is
    called loop-side on all processes, like the orbax contract it
    replaces).

    The copy is load-bearing, not defensive: on the CPU backend
    ``device_get`` returns ZERO-COPY views of device buffers, and the
    train step DONATES its input state — without the copy the writer
    thread would read buffers XLA has already reused for the next step
    (observed as a hard segfault in the resume test)."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        x = _replicate_global(x)
    return np.array(jax.device_get(x), copy=True)


# ---------------------------------------------------------------------------
# Scan / validate
# ---------------------------------------------------------------------------

_torn_announced: set[str] = set()


def _announce_torn(path: str, reason: str) -> None:
    if path in _torn_announced:
        return
    _torn_announced.add(path)
    print(
        json.dumps(
            {"event": "ckpt_torn_skipped", "dir": path, "reason": reason}
        ),
        file=sys.stderr, flush=True,
    )


def _load_manifest(ckpt_dir: str) -> dict | None:
    """Manifest of one step dir iff it validates; None (+ one structured
    stderr line) for a torn dir.  Validation = manifest parses, carries
    this format, and every leaf file exists at its recorded size — which
    the write protocol guarantees for any published dir; failure means a
    kill before publish (no manifest) or external damage."""
    path = os.path.join(ckpt_dir, "manifest.json")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        _announce_torn(ckpt_dir, "no manifest (write never completed)")
        return None
    except (json.JSONDecodeError, OSError) as e:
        _announce_torn(ckpt_dir, f"unreadable manifest: {e!r}")
        return None
    if manifest.get("format") != FORMAT:
        _announce_torn(ckpt_dir, f"unknown format {manifest.get('format')!r}")
        return None
    for entry in manifest.get("leaves", []):
        fpath = os.path.join(ckpt_dir, entry["file"])
        try:
            size = os.path.getsize(fpath)
        except OSError:
            _announce_torn(ckpt_dir, f"missing leaf file {entry['file']}")
            return None
        if size != entry["file_bytes"]:
            _announce_torn(
                ckpt_dir,
                f"leaf {entry['file']} is {size} bytes, manifest says "
                f"{entry['file_bytes']} (truncated?)",
            )
            return None
    return manifest


def _scan_validated(directory: str) -> list[tuple[int, str, dict]]:
    """Valid (step, dir, manifest) triples, ascending by step — ONE
    validation pass; consumers reuse the loaded manifest instead of
    re-validating (which would both re-pay the I/O and open a window
    where a dir damaged between the two reads returns None into a
    crash instead of the clean torn-skip path)."""
    out = []
    try:
        names = os.listdir(directory)
    except FileNotFoundError:
        return []
    for name in names:
        m = _STEP_DIR_RE.match(name)
        if not m:
            continue
        path = os.path.join(directory, name)
        manifest = _load_manifest(path)
        if manifest is not None:
            out.append((int(m.group(1)), path, manifest))
    return sorted(out, key=lambda t: t[0])


def scan_checkpoints(directory: str) -> list[tuple[int, str]]:
    """Valid (step, dir) pairs under ``directory``, ascending by step.
    Torn/in-progress dirs are skipped (announced once per process)."""
    return [(s, p) for s, p, _ in _scan_validated(directory)]


def latest_step(directory: str) -> int | None:
    """Latest restorable checkpointed step under ``directory``, or None."""
    ckpts = scan_checkpoints(directory)
    return ckpts[-1][0] if ckpts else None


def read_manifest(directory: str, step: int | None = None) -> dict | None:
    """The (validated) manifest of ``step`` (default: latest), or None.
    The cheap peek path — ``train.py --resume-elastic`` reads the saved
    data-order metadata from here before building the input pipeline."""
    ckpts = _scan_validated(directory)
    if not ckpts:
        return None
    if step is None:
        return ckpts[-1][2]
    for s, _, manifest in ckpts:
        if s == step:
            return manifest
    return None


# ---------------------------------------------------------------------------
# The write protocol
# ---------------------------------------------------------------------------


def _write_step_dir(
    directory: str,
    step: int,
    leaves: list[tuple[str, np.ndarray]],
    zero_world_size: int | None,
    metadata: Mapping[str, Any] | None,
) -> str:
    """Write one checkpoint with the crash-safe protocol; returns the
    published dir.  Runs in the writer thread (async) or the caller
    thread (sync escape hatch) — process 0 only."""
    final = os.path.join(directory, f"ckpt-{step}")
    tmp = os.path.join(directory, f"{_TMP_PREFIX}{step}-{os.getpid()}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    entries = []
    mid = max(1, len(leaves) // 2)
    for i, (path, arr) in enumerate(leaves):
        if i == mid:
            # One deterministic mid-write chaos point per save (a torn
            # half-written dir is the state this phase must leave safe).
            _chaos_point("tmp_write")
        fname = f"leaf_{i:05d}.npy"
        fpath = os.path.join(tmp, fname)
        with open(fpath, "wb") as f:
            np.save(f, arr)
            f.flush()
            os.fsync(f.fileno())
        entries.append(
            {
                "path": path,
                "file": fname,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "file_bytes": os.path.getsize(fpath),
                "crc32": zlib.crc32(np.ascontiguousarray(arr).data) & 0xFFFFFFFF,
            }
        )
    _chaos_point("manifest_commit")
    # The manifest is the commit record: written + fsync'd LAST, inside
    # the tmp dir, so no published dir can exist without one and no dir
    # with one can lack its bytes.
    atomic_write_json(
        os.path.join(tmp, "manifest.json"),
        {
            "format": FORMAT,
            "version": FORMAT_VERSION,
            "step": step,
            "zero_world_size": zero_world_size,
            "metadata": dict(metadata or {}),
            "leaves": entries,
        },
        indent=1,
    )
    _chaos_point("rename")
    if os.path.exists(final):
        # A re-save of an already-PUBLISHED step (the epilogue's force
        # save after an interval save, a healed run re-reaching its
        # restore step).  If the existing dir validates, keep it and
        # drop ours: deleting a valid checkpoint before the rename would
        # open a kill window with NEITHER copy on disk — the exact
        # protocol violation this module exists to rule out.  Only a
        # TORN existing dir (which holds nothing restorable) is removed.
        if _load_manifest(final) is not None:
            shutil.rmtree(tmp, ignore_errors=True)
            return final
        shutil.rmtree(final)
    os.rename(tmp, final)
    _chaos_point("finalize")
    fsync_dir(directory)
    return final


def _gc(directory: str, max_to_keep: int) -> None:
    """Drop checkpoints beyond ``max_to_keep`` and stale tmp dirs (a
    previous process's interrupted writes; OUR tmp was just renamed)."""
    ckpts = scan_checkpoints(directory)
    for _, path in ckpts[:-max_to_keep] if max_to_keep > 0 else []:
        shutil.rmtree(path, ignore_errors=True)
    for name in os.listdir(directory):
        if name.startswith(_TMP_PREFIX):
            shutil.rmtree(os.path.join(directory, name), ignore_errors=True)


# ---------------------------------------------------------------------------
# The manager
# ---------------------------------------------------------------------------


class CheckpointManager:
    """Crash-safe (async by default) TrainState checkpointing.

    API-compatible with the orbax-era manager (save/should_save/restore/
    restore_arrays/latest_step/wait/close) plus:

    - ``metadata``: dict recorded in every manifest (train.py stores the
      data-order facts ``--resume-elastic`` re-derives from);
    - ``sink``: optional EventSink — the writer emits one structured
      ``ckpt_saved`` event per landed checkpoint (step, write seconds,
      bytes), the artifact the RUNBOOK triage reads;
    - ``restore()`` is world-size-elastic for the optimizer state (see
      module docstring) and returns HOST numpy leaves — placement onto a
      mesh is the caller's job (run_training's replication block).
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        save_interval_steps: int = 1,
        metadata: Mapping[str, Any] | None = None,
        sink: Any | None = None,
        async_save: bool | None = None,
    ):
        self._directory = directory
        self._max_to_keep = max_to_keep
        self._interval = max(1, int(save_interval_steps))
        self._metadata = dict(metadata or {})
        self._sink = sink
        self._async = _async_default() if async_save is None else async_save
        self._is_writer = jax.process_index() == 0
        if self._is_writer:
            os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._error: BaseException | None = None
        self._last_queued: int | None = latest_step(directory)
        # Writer thread state (started lazily on the first async save).
        self._work: queue.Queue = queue.Queue(maxsize=2)
        self._inflight = threading.Event()
        self._done = threading.Condition()
        self._thread: threading.Thread | None = None
        self._closed = False

    @property
    def directory(self) -> str:
        return self._directory

    # ---- save ------------------------------------------------------------

    def should_save(self, step: int) -> bool:
        """Would ``save(step)`` actually write (interval/dedup policy)?

        Lets the training loop run pre-save checks (the non-finite-loss
        gate) only when a save is really about to happen.  Pure host
        arithmetic — no disk scan (the latest step is tracked in-memory).
        """
        if step == self._last_queued:
            return False
        return step % self._interval == 0

    def save(
        self,
        state: TrainState,
        step: int | None = None,
        force: bool = False,
        metadata: Mapping[str, Any] | None = None,
    ) -> bool:
        """Snapshot ``state`` and (async) write checkpoint ``step``.

        The snapshot happens HERE, synchronously — under the caller's step
        serialization, so it is exactly the state at ``step`` — then the
        write overlaps whatever the caller does next.  One-behind: a save
        issued while the previous write is still in flight first waits for
        it (bounded by that write's own duration), so host memory holds at
        most one pending checkpoint.  A failed previous write re-raises
        here (the crash channel).
        """
        if self._closed:
            raise RuntimeError("CheckpointManager is closed")
        step = int(jax.device_get(state.step)) if step is None else int(step)
        if not force and not self.should_save(step):
            return False
        self._join_inflight()  # one-behind + surfaces writer errors
        self._raise_pending_error()
        zero_world = _detect_zero_world(state.opt_state)
        flat = _flatten_with_paths(_saveable(state))
        with trace.span("ckpt_snapshot", step=step):
            if self._is_writer:
                leaves = [(path, _host_leaf(leaf)) for path, leaf in flat]
            else:
                # Non-writers only owe the COLLECTIVE half: join the
                # gather for cross-host sharded leaves so process 0 can
                # read the full value.  No device→host copy of the rest
                # — that would burn full-model D2H bandwidth and a
                # checkpoint-sized host allocation on N-1 hosts for
                # bytes nobody writes.
                for _, leaf in flat:
                    if (
                        hasattr(leaf, "is_fully_addressable")
                        and not leaf.is_fully_addressable
                    ):
                        _replicate_global(leaf)
                leaves = []
        _chaos_point("snapshot")
        self._last_queued = step
        if not self._is_writer:
            return True  # participated in the gather; process 0 writes
        meta = dict(self._metadata)
        if metadata:
            meta.update(metadata)
        if not self._async:
            self._write_one(step, leaves, zero_world, meta)
            self._raise_pending_error()
            return True
        self._ensure_thread()
        self._inflight.set()
        telemetry.record_ckpt_inflight(1)
        self._work.put((step, leaves, zero_world, meta))
        return True

    def _write_one(
        self,
        step: int,
        leaves: list[tuple[str, np.ndarray]],
        zero_world: int | None,
        meta: dict,
    ) -> None:
        t0 = monotonic_s()
        try:
            with trace.span("ckpt_write", step=step):
                _write_step_dir(
                    self._directory, step, leaves, zero_world, meta
                )
                _gc(self._directory, self._max_to_keep)
        except BaseException as e:
            with self._lock:
                self._error = e
            # Crash channel: announce NOW (the loop may be minutes from
            # its next save), re-raise at the next save()/wait()/close().
            print(
                json.dumps(
                    {"event": "ckpt_write_error", "step": step,
                     "error": repr(e)[:500]}
                ),
                file=sys.stderr, flush=True,
            )
        else:
            dt = monotonic_s() - t0
            total_bytes = sum(arr.nbytes for _, arr in leaves)
            telemetry.record_ckpt_save(step, dt, total_bytes)
            event = getattr(self._sink, "event", None)
            if event is not None:
                try:
                    event(
                        "ckpt_saved", step=step, write_s=round(dt, 4),
                        bytes=total_bytes,
                    )
                except Exception:
                    pass  # a broken sink must not fail the save

    # ---- the writer thread ----------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        hb = watchdog.register("ckpt-writer")
        hb.idle()

        def run() -> None:
            try:
                while True:
                    item = self._work.get()
                    if item is None:
                        return
                    hb.beat()
                    self._write_one(*item)
                    hb.idle()
                    telemetry.record_ckpt_inflight(0)
                    self._inflight.clear()
                    with self._done:
                        self._done.notify_all()
            except BaseException as e:  # never die silently (error contract)
                with self._lock:
                    if self._error is None:
                        self._error = e
                telemetry.record_ckpt_inflight(0)
                self._inflight.clear()
                with self._done:
                    self._done.notify_all()
                print(
                    json.dumps(
                        {"event": "ckpt_writer_crashed",
                         "error": repr(e)[:500]}
                    ),
                    file=sys.stderr, flush=True,
                )
                raise
            finally:
                hb.close()

        # watchdog: hb registered above (ckpt-writer); beats per write,
        # idle between saves, closed in run()'s finally.
        self._thread = threading.Thread(
            target=run, daemon=True, name="ckpt-writer"
        )
        self._thread.start()

    def _join_inflight(self) -> None:
        while self._inflight.is_set():
            with self._done:
                self._done.wait(timeout=0.5)

    def _raise_pending_error(self) -> None:
        with self._lock:
            error, self._error = self._error, None
        if error is not None:
            raise RuntimeError(
                "checkpoint write failed (root cause chained)"
            ) from error

    def wait(self) -> None:
        """Block until in-flight saves land; re-raise a failed write."""
        self._join_inflight()
        self._raise_pending_error()

    def close(self) -> None:
        if self._closed:
            return
        self._join_inflight()
        if self._thread is not None and self._thread.is_alive():
            self._work.put(None)
            self._thread.join(timeout=30)
        self._closed = True
        self._raise_pending_error()

    # ---- restore ---------------------------------------------------------

    def latest_step(self) -> int | None:
        return latest_step(self._directory)

    def _target(self, step: int | None) -> tuple[int, str, dict]:
        ckpts = _scan_validated(self._directory)
        if step is not None:
            for s, path, manifest in ckpts:
                if s == step:
                    return s, path, manifest
            raise FileNotFoundError(
                f"no restorable checkpoint for step {step} in "
                f"{self._directory}"
            )
        if not ckpts:
            raise FileNotFoundError(
                f"no checkpoint in {self._directory}"
            )
        return ckpts[-1]

    def restore(self, state: TrainState, step: int | None = None) -> TrainState:
        """Restore into the structure of ``state`` (the shapes template).

        ``state`` must be a freshly-initialized TrainState for the same
        model and optimizer — but NOT necessarily the same world layout:
        optimizer-state leaves are re-laid into the template's layout
        (``reshard_flat_leaf``), so a ZeRO checkpoint from world N
        restores into a world-M template or a replicated one, and vice
        versa.  Returns host numpy leaves; the caller places them (the
        loop's replication block / an explicit device_put).
        """
        _, ckpt_dir, manifest = self._target(step)
        saved = self._load_leaves(ckpt_dir, manifest)
        template = _saveable(state)
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        t_paths = [jax.tree_util.keystr(p) for p, _ in flat]
        is_comm = lambda p: p.startswith("['comm_state']")  # noqa: E731
        missing = [p for p in t_paths if p not in saved]
        extra = [p for p in saved if p not in set(t_paths)]
        # Comm EF residuals (ISSUE 13) are ADVISORY state: a template/
        # checkpoint mismatch there (compression newly enabled, mode or
        # bucket layout changed, or a ZeRO<->DP switch re-keying the
        # residuals) must not refuse a restore — the residuals reset to
        # zero (EF re-converges in a handful of steps) and exactly ONE
        # structured ef_reset event says so.  Params/optimizer mismatch
        # still refuses loudly below.
        comm_missing = {p for p in missing if is_comm(p)}
        comm_dropped = [p for p in extra if is_comm(p)]
        missing = [p for p in missing if not is_comm(p)]
        extra = [p for p in extra if not is_comm(p)]
        if missing or extra:
            raise ValueError(
                f"checkpoint {ckpt_dir} does not match this model/"
                f"optimizer: missing leaves {missing[:5]}"
                f"{'...' if len(missing) > 5 else ''}, unexpected leaves "
                f"{extra[:5]}{'...' if len(extra) > 5 else ''}"
            )
        comm_reset = sorted(comm_missing)
        out = []
        for path, leaf in zip(t_paths, (l for _, l in flat)):
            shape = tuple(int(d) for d in np.shape(leaf))
            dtype = np.dtype(getattr(leaf, "dtype", np.asarray(leaf).dtype))
            if path in comm_missing:
                out.append(np.zeros(shape, dtype))
                continue
            arr = saved[path]
            if is_comm(path):
                # Comm residuals reshard like ZeRO slots (same flat
                # padding-is-zeros layout) — but they are ADVISORY: a
                # re-lay that would drop real content (bucket size
                # changed under the same key) zeroes the leaf instead
                # of refusing the restore, counted into the single
                # ef_reset record below.
                try:
                    out.append(reshard_flat_leaf(arr, shape, dtype, path))
                except ValueError:
                    out.append(np.zeros(shape, dtype))
                    comm_reset.append(path)
                continue
            if path.startswith("['opt_state']"):
                # Flat ZeRO-layout optimizer slots re-lay into the
                # template's world size; dropping real data REFUSES.
                out.append(reshard_flat_leaf(arr, shape, dtype, path))
                continue
            if arr.shape != shape or arr.dtype != dtype:
                raise ValueError(
                    f"checkpoint leaf {path}: saved {arr.shape}/{arr.dtype}"
                    f" != expected {shape}/{dtype} — a different model was "
                    "checkpointed here"
                )
            out.append(arr)
        if comm_reset or comm_dropped:
            self._announce_ef_reset(ckpt_dir, comm_reset, comm_dropped)
        restored = jax.tree_util.tree_unflatten(treedef, out)
        return dataclasses.replace(
            state,
            step=restored["step"],
            params=restored["params"],
            batch_stats=restored["batch_stats"],
            opt_state=restored["opt_state"],
            comm_state=restored["comm_state"],
        )

    def _announce_ef_reset(
        self, ckpt_dir: str, zeroed: list, dropped: list
    ) -> None:
        """ONE structured ef_reset record per restore: the EF residual
        state could not be carried over (see restore()) and was zeroed/
        dropped — visible in metrics.jsonl (sink) and on stderr."""
        payload = {
            "event": "ef_reset",
            "dir": ckpt_dir,
            "zeroed": len(zeroed),
            "dropped": len(dropped),
            "reason": (
                "checkpoint comm_state does not match this run's comm "
                "policy/layout; error-feedback residuals reset to zero "
                "(EF re-converges within a few steps)"
            ),
        }
        print(json.dumps(payload), file=sys.stderr, flush=True)
        event = getattr(self._sink, "event", None)
        if event is not None:
            try:
                fields = {k: v for k, v in payload.items() if k != "event"}
                event("ef_reset", **fields)
            except Exception:
                pass  # a broken sink must not fail the restore

    def restore_arrays(self, step: int | None = None) -> dict[str, Any]:
        """The saved tree as nested host dicts, no template needed.

        For consumers that must not depend on the optimizer that produced
        the snapshot — the export path (convert_model.py) keeps only
        params/batch_stats/step.  ``opt_state`` leaves are returned under
        a FLAT ``{keypath: array}`` dict (their pytree structure needs the
        optimizer to rebuild; no template-free consumer wants them).
        """
        _, ckpt_dir, manifest = self._target(step)
        saved = self._load_leaves(ckpt_dir, manifest)
        out: dict[str, Any] = {"opt_state": {}}
        key_re = re.compile(r"\['([^']*)'\]")
        for path, arr in saved.items():
            if path.startswith("['opt_state']"):
                out["opt_state"][path] = arr
                continue
            keys = key_re.findall(path)
            if path == "['step']":
                out["step"] = arr
                continue
            node = out
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            node[keys[-1]] = arr
        out.setdefault("params", {})
        out.setdefault("batch_stats", {})
        return out

    @staticmethod
    def _load_leaves(ckpt_dir: str, manifest: dict) -> dict[str, np.ndarray]:
        verify = os.environ.get("RETINANET_CKPT_VERIFY", "0").lower() in (
            "1", "true",
        )
        out = {}
        for entry in manifest["leaves"]:
            arr = np.load(os.path.join(ckpt_dir, entry["file"]))
            if verify:
                crc = zlib.crc32(np.ascontiguousarray(arr).data) & 0xFFFFFFFF
                if crc != entry["crc32"]:
                    raise ValueError(
                        f"checkpoint leaf {entry['path']} in {ckpt_dir} "
                        f"fails its crc32 (bit rot / external damage)"
                    )
            out[entry["path"]] = arr
        return out
