"""Multiprocess shared-memory input pipeline: the GIL-free producer.

Why this exists: the thread-pool producer in ``pipeline.py`` plateaus at
~2 workers because PIL's JPEG decode holds the GIL (cv2's resize releases
it, but decode dominates), capping a host below what one chip's train step
consumes (no cell measures the host pipeline yet: PERF.md section 7).  Here
the decode/augment/resize fan-out runs in ``num_worker_procs`` WORKER
PROCESSES instead, each writing its decoded image directly into a
preallocated POSIX shared-memory ring buffer, so the only things crossing
the process boundary by pickling are a few ints and the (tiny) gt arrays —
never an image.

Architecture
------------
- One shared-memory **slab per bucket shape**: ``(slots, H, W, 3)`` uint8
  (float32 under ``host_normalize``).  Slots are a parent-managed free list;
  a worker writes example ``seq`` into its assigned slot and reports
  ``(seq, h, w, boxes, labels, scale)`` on the result queue.
- The **parent coordinator** (a thread, same shape as the thread-path
  producer) plans batches with the exact same deterministic
  ``batch_plans``/``example_rng`` helpers the thread path uses, assigns
  slots, and assembles finished batches IN SUBMISSION ORDER via the shared
  ``_assemble`` — so the two paths are bit-identical for a fixed seed.
- ``PipelineStats`` is tracked centrally at assembly (truncation is counted
  where the padding happens), so counters need no cross-process machinery.

Robustness contract (tested in tests/unit/test_shm_pipeline.py):
- a worker CRASH surfaces as a RuntimeError in the consumer within ~a
  second (liveness poll each pump iteration), after children are reaped and
  the shared memory unlinked;
- a worker WEDGE (alive but stuck) trips ``config.worker_timeout`` on the
  head-of-line batch — never a silent hang;
- ``close()`` is idempotent and reaps every child and /dev/shm segment;
  a ``weakref.finalize`` backstops leak-free teardown when the consumer
  drops the iterator without closing it.

Workers are ``spawn``ed by default: forking a parent that has initialized
JAX/XLA (thread pools, a possibly-live TPU client) is unsafe, and the
workers only need the data layer (numpy/PIL/cv2) — they never import jax.
"""

from __future__ import annotations

import os
import queue
import threading
import time
import traceback
import uuid
import weakref
from collections import deque
from multiprocessing import shared_memory

import numpy as np

from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
    Batch,
    PipelineConfig,
    PipelineStats,
    _assemble,
    _pad_batch,
    batch_plans,
    example_rng,
    load_example,
    stop_gated_put,
)
from batchai_retinanet_horovod_coco_tpu.obs import trace, watchdog

_SENTINEL = object()
_SHM_PREFIX = "bretshm"  # distinctive: tests scan /dev/shm for leaks


class _StopRequested(Exception):
    """Internal: the consumer closed the pipeline; unwind the producer."""


def _attach_shm(name: str) -> shared_memory.SharedMemory:
    """Attach to an existing segment WITHOUT registering it with this
    process's resource tracker.

    The parent owns the segments (creates once, unlinks once).  Spawned
    children INHERIT the parent's resource-tracker process, so a child
    attach that registers (as pre-3.13 ``SharedMemory`` unconditionally
    does) plus the matching unregister-after-attach workaround races the
    parent's own unlink-time unregister — observed as KeyError noise in the
    shared tracker.  Python 3.13 has ``track=False`` for exactly this; on
    older versions the clean equivalent is to suppress the registration
    call itself for the duration of the attach.
    """
    try:
        return shared_memory.SharedMemory(name=name, track=False)  # py>=3.13
    except TypeError:
        pass
    from multiprocessing import resource_tracker

    orig_register = resource_tracker.register

    def _no_shm_register(rname, rtype):
        if rtype != "shared_memory":
            orig_register(rname, rtype)

    resource_tracker.register = _no_shm_register
    try:
        return shared_memory.SharedMemory(name=name)
    finally:
        resource_tracker.register = orig_register


def _worker_main(
    worker_id: int,
    dataset,
    config: PipelineConfig,
    train: bool,
    slabs: list[tuple[str, tuple[int, ...], str]],
    task_q,
    result_q,
    stop_evt,
) -> None:
    """Worker-process loop: task → decode/augment/resize → shm slot.

    Tasks are ``(seq, epoch, idx, bucket_id, slot)``; the heavy image bytes
    land in ``slabs[bucket_id][slot]`` and only the small result tuple is
    pickled back.  Any failure is reported on the result queue (with the
    traceback) before a hard exit, so the parent can re-raise it verbatim.
    """
    import signal

    signal.signal(signal.SIGINT, signal.SIG_IGN)  # parent owns Ctrl-C
    # Tracing self-enables iff the parent exported the obs env contract
    # before the spawn; the decode spans land in this process's own trace
    # file (exported on clean exit) and merge into the parent's timeline.
    # obs.trace never imports jax, preserving this worker's no-jax rule.
    tracing = trace.maybe_configure_from_env(f"shm-worker-{worker_id}")
    try:
        from batchai_retinanet_horovod_coco_tpu.data.transforms import cv2

        if cv2 is not None:
            # One core per worker: N workers already saturate N cores, and
            # cv2's own thread pool would only fight them for cycles.
            cv2.setNumThreads(1)
    except Exception:
        pass
    shms: list[shared_memory.SharedMemory] = []
    try:
        views = []
        for name, shape, dtype in slabs:
            shm = _attach_shm(name)
            shms.append(shm)
            views.append(np.ndarray(shape, dtype=np.dtype(dtype), buffer=shm.buf))
        while not stop_evt.is_set():
            try:
                task = task_q.get(timeout=0.2)
            except queue.Empty:
                continue
            if task is None:
                break
            seq, epoch, idx, bucket_id, slot = task
            record = dataset.records[idx]
            with trace.span("decode"):
                img, boxes, labels, scale = load_example(
                    dataset,
                    record,
                    config,
                    example_rng(config, train, epoch, idx),
                    config.buckets[bucket_id],
                )
            h, w = img.shape[:2]
            with trace.span("shm_write"):
                views[bucket_id][slot, :h, :w] = img
            result_q.put(("ok", seq, h, w, boxes, labels, scale))
        if tracing:
            trace.export()  # clean exit only; a crashed worker's trace is
            # forfeit (os._exit below), the parent's diagnosis carries on
    except BaseException:
        try:
            result_q.put(("err", worker_id, traceback.format_exc()))
            # Flush the queue's feeder thread BEFORE the hard exit, or the
            # error report can die in the buffer and the parent only sees
            # a generic "worker died" without the traceback.
            result_q.close()
            result_q.join_thread()
        except Exception:
            pass
        os._exit(1)
    finally:
        del views  # drop buffer exports before closing the mappings
        for shm in shms:
            try:
                shm.close()
            except BufferError:
                pass


def _finalize_pipeline(stop, mp_stop, procs, task_q, result_q, shms, views):
    """GC/close() teardown: also stops the coordinator thread.

    The producer's own exit path calls ``_cleanup_resources`` directly
    instead — it must NOT set ``stop``, because after an error it still has
    one exception to deliver through the (stop-gated) output queue.
    """
    stop.set()
    _cleanup_resources(mp_stop, procs, task_q, result_q, shms, views)


def _cleanup_resources(mp_stop, procs, task_q, result_q, shms, views) -> None:
    """Reap children and unlink shared memory.  Idempotent; never raises.

    Runs (first-come, all tolerated) from the producer's exit path, from
    ``close()``, and from the iterator's ``weakref.finalize`` backstop.
    """
    mp_stop.set()
    for _ in procs:
        try:
            task_q.put_nowait(None)
        except Exception:
            pass
    deadline = trace.monotonic_s() + 5.0
    for p in procs:
        try:
            p.join(timeout=max(0.1, deadline - trace.monotonic_s()))
        except Exception:
            pass
    for p in procs:
        try:
            if p.is_alive():
                p.terminate()
        except Exception:
            pass
    for p in procs:
        try:
            p.join(timeout=2.0)
            if p.is_alive():
                p.kill()
                p.join(timeout=2.0)
        except Exception:
            pass
    for q in (task_q, result_q):
        try:
            q.cancel_join_thread()
            q.close()
        except Exception:
            pass
    views.clear()  # release buffer exports so the mmaps can close
    for shm in shms:
        try:
            shm.close()
        except Exception:
            pass
        try:
            shm.unlink()
        except FileNotFoundError:
            pass
        except Exception:
            pass


class _ShmPipeline:
    """Iterator over batches produced by worker processes.

    Same surface as the thread path's ``_PipelineIterator``: iteration,
    live ``stats``, ``close()``.  Extra (for tests/tooling): ``processes``
    (the live ``multiprocessing.Process`` objects) and ``shm_names``.
    """

    def __init__(self, dataset, config: PipelineConfig, train: bool):
        import multiprocessing as mp

        if config.num_worker_procs <= 0:
            raise ValueError("build_shm_pipeline needs num_worker_procs > 0")
        self._config = config
        self._dataset = dataset
        self._train = train
        self.stats = PipelineStats()
        ctx = mp.get_context(config.mp_start_method)

        # Mirror the thread path's in-flight batch window so neither path
        # drains its workers at a batch boundary; +1 batch of slots covers
        # the batch currently being planned (its slots are allocated before
        # the batch joins the in-flight deque).
        bs = max(1, config.batch_size)
        self._max_inflight = max(
            2, -(-config.num_worker_procs // bs) + 1
        )
        self._slots_per_bucket = bs * (self._max_inflight + 1)
        dtype = np.float32 if config.host_normalize else np.uint8
        run_id = f"{_SHM_PREFIX}{os.getpid()}_{uuid.uuid4().hex[:6]}"
        self._shms: list[shared_memory.SharedMemory] = []
        self._views: list[np.ndarray] = []
        self._slab_spec: list[tuple[str, tuple[int, ...], str]] = []
        try:
            for k, (bh, bw) in enumerate(config.buckets):
                shape = (self._slots_per_bucket, bh, bw, 3)
                nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
                shm = shared_memory.SharedMemory(
                    name=f"{run_id}_{k}", create=True, size=nbytes
                )
                self._shms.append(shm)
                self._views.append(
                    np.ndarray(shape, dtype=dtype, buffer=shm.buf)
                )
                self._slab_spec.append((shm.name, shape, np.dtype(dtype).str))
        except BaseException:
            # A partway create failure (undersized /dev/shm — Docker
            # defaults to 64 MB — raises ENOSPC on slab k) happens BEFORE
            # the finalizer below exists; without this, slabs 0..k-1 would
            # outlive the process in /dev/shm.
            self._views.clear()
            for shm in self._shms:
                try:
                    shm.close()
                except Exception:
                    pass
                try:
                    shm.unlink()
                except Exception:
                    pass
            raise
        self.shm_names = [s.name for s in self._shms]
        self._bucket_ids = {b: i for i, b in enumerate(config.buckets)}

        # lint: bounded-queues: in-flight tasks are bounded by the slot
        # tokens — the coordinator only submits while it holds a free shm
        # slot, so depth ≤ slots_per_bucket × len(buckets) by protocol.
        self._task_q = ctx.Queue()
        # lint: bounded-queues: one result per in-flight task; bounded by
        # the same slot-token protocol as the task queue above.
        self._result_q = ctx.Queue()
        self._mp_stop = ctx.Event()
        # watchdog-exempt (workers): decode workers heartbeat IMPLICITLY
        # through the result queue — the coordinator (registered in
        # _producer) beats a shm-pipe component per arriving result, so a
        # dead/wedged fleet stops that heartbeat within one task.
        self.processes = [
            ctx.Process(
                target=_worker_main,
                args=(
                    w, dataset, config, train, self._slab_spec,
                    self._task_q, self._result_q, self._mp_stop,
                ),
                daemon=True,
                name=f"shm-pipe-worker-{w}",
            )
            for w in range(config.num_worker_procs)
        ]

        # Producer-side state (all touched only by the coordinator thread).
        self._out: queue.Queue = queue.Queue(maxsize=max(1, config.prefetch))
        self._stop = threading.Event()
        self._free: list[deque] = [
            deque(range(self._slots_per_bucket)) for _ in config.buckets
        ]
        self._inflight: deque = deque()
        self._results: dict[int, tuple] = {}
        self._seq_slot: dict[int, tuple[int, int]] = {}
        self._next_seq = 0
        self._finished = False  # set once the stream terminally ended
        self._last_liveness = 0.0  # last worker-liveness poll (monotonic)

        # Backstop BEFORE any child starts: if a spawn fails halfway, the
        # half-built pipeline still reaps and unlinks at GC.
        self._finalizer = weakref.finalize(
            self,
            _finalize_pipeline,
            self._stop,
            self._mp_stop,
            self.processes,
            self._task_q,
            self._result_q,
            self._shms,
            self._views,
        )
        for p in self.processes:
            p.start()
        self._hb = None  # registered by the coordinator thread itself
        # watchdog: registers in _producer() at thread start.
        self._thread = threading.Thread(
            target=self._producer, daemon=True, name="shm-pipe-coordinator"
        )
        self._thread.start()

    # ---- consumer surface ------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self) -> Batch:
        if self._finished:
            # Match generator semantics: once the stream ended (epoch
            # sentinel or a delivered exception), further next() calls
            # raise StopIteration instead of blocking on a dead queue.
            raise StopIteration
        item = self._out.get()
        if item is _SENTINEL:
            self._finished = True
            self.close()
            raise StopIteration
        if isinstance(item, BaseException):
            # Children are already reaped and shm unlinked (the producer
            # cleans up BEFORE delivering the exception); close() here just
            # stops the coordinator thread.
            self._finished = True
            self.close()
            raise item
        return item

    def close(self) -> None:
        """Stop the coordinator, reap all children, unlink all segments."""
        self._stop.set()
        if (
            self._thread.is_alive()
            and self._thread is not threading.current_thread()
        ):
            self._thread.join(timeout=10)
        self._finalizer()

    # ---- producer (coordinator thread) -----------------------------------

    def _put(self, item) -> bool:
        return stop_gated_put(self._out, item, self._stop)

    def _check_workers(self) -> None:
        self._last_liveness = trace.monotonic_s()
        for p in self.processes:
            if not p.is_alive():
                # Prefer the worker's own report: a worker that errored
                # queues a traceback then exits, and the liveness poll can
                # win the race against the queue's feeder thread.  Grace-
                # drain briefly before falling back to the generic verdict.
                grace = trace.monotonic_s() + 1.0
                while trace.monotonic_s() < grace:
                    try:
                        msg = self._result_q.get_nowait()
                    except queue.Empty:
                        time.sleep(0.05)
                        continue
                    if msg[0] == "err":
                        raise RuntimeError(
                            f"input-pipeline worker {msg[1]} failed:\n"
                            f"{msg[2]}"
                        )
                    _, seq, h, w, boxes, labels, scale = msg
                    self._results[seq] = (h, w, boxes, labels, scale)
                raise RuntimeError(
                    f"input-pipeline worker {p.name} (pid {p.pid}) died "
                    f"unexpectedly with exit code {p.exitcode}; the decode "
                    "fleet is no longer intact, aborting the run"
                )

    def _pump_until(self, cond) -> None:
        """Drain worker results until ``cond()`` holds.

        Raises on consumer stop, worker error, worker death, or when the
        condition makes no progress within ``config.worker_timeout`` —
        the bounded-stall guarantee (a wedged worker can stall the
        head-of-line batch forever; a timeout is the only way to surface
        an alive-but-stuck child).
        """
        deadline = trace.monotonic_s() + self._config.worker_timeout
        while not cond():
            if self._stop.is_set():
                raise _StopRequested
            # Liveness at a bounded cadence even under continuous result
            # flow: with one dead worker and N-1 healthy ones the result
            # queue can stay non-empty indefinitely, and an idle-poll-only
            # check would miss the death until the stream happened to
            # drain (observed as a 30s+ detection gap on a loaded box).
            if trace.monotonic_s() - self._last_liveness > 0.5:
                self._check_workers()
            try:
                msg = self._result_q.get(timeout=0.1)
            except queue.Empty:
                msg = None
            if msg is not None:
                if msg[0] == "err":
                    raise RuntimeError(
                        f"input-pipeline worker {msg[1]} failed:\n{msg[2]}"
                    )
                _, seq, h, w, boxes, labels, scale = msg
                self._results[seq] = (h, w, boxes, labels, scale)
                # Any arriving result IS progress: the timeout bounds a
                # STALL, not total head-batch latency (expensive decodes
                # trickling in steadily must never trip it).  The same
                # arrival is the worker fleet's implicit watchdog
                # heartbeat (workers never register themselves).
                if self._hb is not None:
                    self._hb.beat()
                deadline = trace.monotonic_s() + self._config.worker_timeout
                continue
            self._check_workers()
            if trace.monotonic_s() > deadline:
                raise RuntimeError(
                    "input pipeline stalled: no progress on the head batch "
                    f"within worker_timeout={self._config.worker_timeout}s "
                    f"({self._config.num_worker_procs} workers alive but "
                    "not delivering; a wedged worker or a pathologically "
                    "slow decode — raise PipelineConfig.worker_timeout if "
                    "the latter is expected)"
                )

    def _acquire_slot(self, bucket_id: int) -> int:
        while not self._free[bucket_id]:
            # Slots recycle at assembly; flushing the head batch is the
            # only way to mint free slots.  Deadlock-free: slots_per_bucket
            # > max_inflight * batch_size guarantees the head batch's tasks
            # are always fully submitted, and tasks are consumed FIFO.
            self._flush_head()
        return self._free[bucket_id].popleft()

    def _flush_head(self) -> None:
        bucket, bucket_id, seqs, ids, short = self._inflight[0]
        with trace.span("shm_head_wait"):
            self._pump_until(lambda: all(s in self._results for s in seqs))
        self._inflight.popleft()
        examples = []
        slots = []
        for s in seqs:
            h, w, boxes, labels, scale = self._results.pop(s)
            b_id, slot = self._seq_slot.pop(s)
            slots.append(slot)
            examples.append(
                (self._views[b_id][slot, :h, :w], boxes, labels, scale)
            )
        # _assemble copies the shm views into a fresh batch, so the slots
        # can recycle immediately and the consumer never aliases the ring.
        with trace.span("shm_assemble"):
            batch = _assemble(examples, ids, bucket, self._config, self.stats)
        self._free[bucket_id].extend(slots)
        if short:
            batch = _pad_batch(batch, self._config.batch_size)
        if trace.enabled():
            trace.counter("shm.out_qsize", self._out.qsize())
            trace.counter("shm.inflight_batches", len(self._inflight))
        if self._hb is not None:
            self._hb.idle()  # blocked on a full output queue = backpressure
        ok = self._put(batch)
        if self._hb is not None:
            self._hb.beat()
        if not ok:
            raise _StopRequested

    def _produce(self) -> None:
        config, train = self._config, self._train
        epoch = 0
        # Elastic-resume fast-forward: same plan-level skip as the thread
        # producer (data/pipeline.py) — bit-identical streams require the
        # two paths to skip identically.
        to_skip = config.skip_batches if train else 0
        while not self._stop.is_set():
            for bucket, chunk, ids, short in batch_plans(
                self._dataset, config, train, epoch
            ):
                if to_skip > 0:
                    to_skip -= 1
                    continue
                bucket_id = self._bucket_ids[bucket]
                seqs = []
                for i in chunk:
                    slot = self._acquire_slot(bucket_id)
                    seq = self._next_seq
                    self._next_seq += 1
                    self._seq_slot[seq] = (bucket_id, slot)
                    seqs.append(seq)
                    self._task_q.put((seq, epoch, int(i), bucket_id, slot))
                self._inflight.append((bucket, bucket_id, seqs, ids, short))
                while len(self._inflight) >= self._max_inflight:
                    self._flush_head()
            if not train:
                while self._inflight:
                    self._flush_head()
                self._put(_SENTINEL)
                return
            epoch += 1

    def _cleanup(self) -> None:
        _cleanup_resources(
            self._mp_stop, self.processes, self._task_q, self._result_q,
            self._shms, self._views,
        )

    def _producer(self) -> None:
        self._hb = watchdog.register(
            "shm-pipe-coordinator",
            # One heartbeat covers coordinator AND fleet: it beats on every
            # worker result (_pump_until) and every delivered batch
            # (_flush_head); details snapshot the queue/slot state a stall
            # diagnosis needs.
            details=lambda: {
                "out_qsize": self._out.qsize(),
                "inflight_batches": len(self._inflight),
                "pending_results": len(self._results),
                "workers_alive": sum(p.is_alive() for p in self.processes),
            },
        )
        try:
            self._produce()
        except _StopRequested:
            pass
        except BaseException as exc:
            # Clean up FIRST so that when the consumer sees the exception,
            # the children are already reaped and /dev/shm is already clean
            # (the consumer may be in a test that immediately checks both).
            # Direct _cleanup, NOT the finalizer: the finalizer would set
            # the stop flag, and the stop-gated _put below must still be
            # able to deliver this exception to a live consumer.
            self._cleanup()
            self._put(exc)
            return
        finally:
            self._hb.close()  # a closed pipeline must not look "stalled"
        self._cleanup()


def build_shm_pipeline(
    dataset, config: PipelineConfig, train: bool = True
) -> _ShmPipeline:
    """Multiprocess twin of ``pipeline.build_pipeline`` (its dispatch target
    when ``config.num_worker_procs > 0``) — same batches, same order, same
    bits; decoded by processes instead of GIL-bound threads."""
    return _ShmPipeline(dataset, config, train)
