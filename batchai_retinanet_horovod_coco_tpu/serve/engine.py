"""Executable table + one-behind device dispatch (the serve device layer).

``DetectEngine`` owns one compiled detection program per (shape bucket,
batch size) and nothing else — the TVM lesson (PAPERS.md): a compiled
static-shape program is the deployable unit, and serving is routing into a
small table of them.  Two constructors:

- ``from_export(dir)`` — load a ``convert_model.py`` export directory
  (evaluate/export.py): self-contained StableHLO artifacts, params baked
  in, NO model code needed.  Routing metadata (buckets, batch sizes,
  resize rule, label→category mapping) comes from the manifest.
- ``from_state(model, state, ...)`` — live params, AOT-compiled through
  ``evaluate.detect.compile_detect_fn``.

Both AOT-build every executable at construction and ``warmup()`` runs
each once on zeros — no request ever pays a compile (SURVEY.md §7.3's
static-shape price is paid exactly once, at startup).

``DeviceDispatcher`` is the single device-facing thread: it pulls
assembled batches from a bounded queue and dispatches ONE-BEHIND — batch
N is dispatched before batch N−1's results are pulled, so the host-side
``device_get`` + conversion of N−1 overlap N's forward+NMS on device (the
``evaluate/detect.py`` eval-driver overlap trick, request-path edition).
When the queue runs dry the pending batch is fetched immediately, so the
overlap never costs latency under light load.

In continuous mode (ISSUE 14) the one-behind seam grows into a loop
around a ``DispatchGate``: whenever the device will take the next batch
immediately — it is idle, or the dispatcher is about to block fetching
the only in-flight batch — the gate is set, and the bucket batchers seal
their ASSEMBLING partial batch against it instead of waiting out the
coalescing deadline.  A batch sealed during batch N's fetch is dispatched
the instant N's results land, BEFORE N's conversion, so the device hop
N → N+1 never waits on host-side convert work.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable

import numpy as np

from batchai_retinanet_horovod_coco_tpu.data.pipeline import stop_gated_put
from batchai_retinanet_horovod_coco_tpu.obs import trace, watchdog
from batchai_retinanet_horovod_coco_tpu.serve.common import AssembledBatch
from batchai_retinanet_horovod_coco_tpu.utils.locks import make_lock


class IdentityLabelMap(dict):
    """label → category fallback when no mapping is known (CSV-style
    datasets where labels ARE the category ids)."""

    def __missing__(self, key: int) -> int:
        return key


class DetectEngine:
    """A (bucket, batch) → compiled-program table with routing metadata."""

    def __init__(
        self,
        fns: dict[tuple[int, int], dict[int, Callable]],
        min_side: int,
        max_side: int,
        label_to_cat_id: dict[int, int] | None = None,
        source: str = "live",
        version: str = "live",
    ):
        if not fns:
            raise ValueError("engine needs at least one (bucket, batch) program")
        self._fns = fns
        self.min_side = min_side
        self.max_side = max_side
        self.label_to_cat_id = (
            label_to_cat_id if label_to_cat_id else IdentityLabelMap()
        )
        self.source = source
        # The model/rollout identity the fleet router and canary gate
        # attribute weight by (ISSUE 12): the export manifest's recorded
        # version, the export dir's basename as a fallback on legacy
        # manifests, or "live" for from_state engines.
        self.version = version
        self.buckets: tuple[tuple[int, int], ...] = tuple(sorted(fns))

    # ---- table lookups ---------------------------------------------------

    def batch_sizes(self, hw: tuple[int, int]) -> list[int]:
        return sorted(self._fns[hw])

    def max_batch(self, hw: tuple[int, int]) -> int:
        return max(self._fns[hw])

    def batch_size_for(self, hw: tuple[int, int], n: int) -> int:
        """Smallest compiled batch size that fits ``n`` requests (a lone
        straggler runs at batch 1 when exported); the largest otherwise —
        the batcher never forms more than ``max_batch`` requests."""
        sizes = self.batch_sizes(hw)
        for b in sizes:
            if b >= n:
                return b
        return sizes[-1]

    # ---- device ----------------------------------------------------------

    def dispatch(self, hw: tuple[int, int], images: np.ndarray):
        """Asynchronously dispatch one padded batch; returns device
        Detections (fetch with ``fetch``)."""
        return self._fns[hw][images.shape[0]](images)

    def fetch(self, det):
        """Block until a dispatched batch finishes; numpy Detections."""
        import jax

        return jax.device_get(det)

    def warmup(self) -> None:
        """Run every (bucket, batch) program once on zeros and sync — the
        startup AOT warm that keeps compiles/deserialization-autotune out
        of the request path."""
        import jax

        for hw in self.buckets:
            for b in self.batch_sizes(hw):
                with trace.span(
                    "serve_warmup", bucket=f"{hw[0]}x{hw[1]}", batch=b
                ):
                    jax.block_until_ready(
                        self.dispatch(hw, np.zeros((b, *hw, 3), np.uint8))
                    )

    # ---- constructors ----------------------------------------------------

    @classmethod
    def from_export(cls, export_dir: str) -> "DetectEngine":
        """Engine over a ``convert_model.py`` export directory — needs only
        jax, never the model code or the checkpoint."""
        from batchai_retinanet_horovod_coco_tpu.evaluate.export import (
            load_model,
        )

        from batchai_retinanet_horovod_coco_tpu.ops.nms import Detections

        loaded = load_model(export_dir)
        fns: dict[tuple[int, int], dict[int, Callable]] = {}
        for b, h, w in loaded.buckets():
            raw = loaded.fn(b, (h, w))

            # Exported programs return a bare (boxes, scores, labels,
            # valid) tuple (jax.export flattens the NamedTuple); restore
            # the Detections view the conversion path expects.
            def call(images, _raw=raw):
                return Detections(*_raw(images))

            fns.setdefault((h, w), {})[b] = call
        manifest = loaded.manifest
        raw_map = manifest.get("label_to_cat_id")
        label_map = (
            {int(k): int(v) for k, v in raw_map.items()} if raw_map else None
        )
        buckets = sorted(fns)
        # Legacy manifests predate the recorded resize rule; falling back
        # to the bucket extents keeps routing sane (every image fits SOME
        # bucket) while new exports carry the exact eval-time sides.
        min_side = manifest.get("image_min_side") or min(
            min(hw) for hw in buckets
        )
        max_side = manifest.get("image_max_side") or max(
            max(hw) for hw in buckets
        )
        import os

        version = manifest.get("version") or os.path.basename(
            os.path.normpath(export_dir)
        )
        return cls(
            fns, min_side, max_side, label_map, source=export_dir,
            version=str(version),
        )

    @classmethod
    def from_state(
        cls,
        model,
        state,
        buckets: tuple[tuple[int, int], ...] | None = None,
        batch_sizes: tuple[int, ...] = (8,),
        config=None,
        min_side: int = 800,
        max_side: int = 1333,
        label_to_cat_id: dict[int, int] | None = None,
        mesh=None,
    ) -> "DetectEngine":
        """Engine over live params, AOT-compiled via the shared
        ``compile_detect_fn`` path (one executable per bucket × batch).

        Every bucket gets one executable per entry of ``batch_sizes``;
        all are compiled here, so no request ever compiles.
        """
        from batchai_retinanet_horovod_coco_tpu.data.pipeline import (
            default_buckets,
        )
        from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
            DetectConfig,
            compile_detect_fn,
        )

        if buckets is None:
            buckets = default_buckets(min_side, max_side)
        if config is None:
            config = DetectConfig()
        fns: dict[tuple[int, int], dict[int, Callable]] = {}
        for hw in buckets:
            fns[hw] = {
                b: compile_detect_fn(model, state, hw, b, config, mesh=mesh)
                for b in sorted(set(batch_sizes))
            }
        return cls(fns, min_side, max_side, label_to_cat_id, source="live")


class DispatchGate:
    """The device-readiness handshake between the dispatcher and the
    bucket batchers (continuous mode, ISSUE 14).

    Two signals cross it:

    - **ready** (dispatcher → batchers): the next sealed batch will be
      dispatched immediately — the device is idle, or batch N's results
      just landed.  SET by the dispatcher, CLEARED by whoever consumes
      it (the batcher that seals against it / the dispatcher when a
      batch arrives).  Batchers seal their assembling partial batch the
      moment they see it, so N+1 rides the instant N returns instead of
      padding out the coalescing deadline.
    - **armed** (batchers → dispatcher): at least one bucket pool has
      claimed slots.  The dispatcher uses it to decide whether a brief
      post-fetch handoff wait can yield a batch at all — an idle server
      never pays the wait on its own completion path.
    """

    __slots__ = ("_event", "_lock", "_armed")

    def __init__(self):
        self._event = threading.Event()
        self._lock = make_lock("serve.engine.DispatchGate._lock")
        self._armed: set = set()

    def set_ready(self) -> None:
        self._event.set()

    def clear(self) -> None:
        self._event.clear()

    def is_ready(self) -> bool:
        return self._event.is_set()

    def arm(self, key) -> None:
        with self._lock:
            self._armed.add(key)

    def disarm(self, key) -> None:
        with self._lock:
            self._armed.discard(key)

    def armed(self) -> bool:
        with self._lock:
            return bool(self._armed)


class DeviceDispatcher:
    """The single device thread: bounded in-queue → one-behind dispatch.

    ``on_batch(assembled, detections_np)`` runs HERE, after batch N+1 has
    been dispatched (or immediately when the queue is idle) — conversion
    and future-fulfillment overlap device compute exactly as the eval
    driver's fetch-convert of batch N−1 overlaps batch N's NMS.
    ``on_fatal(exc)`` routes a crash to the frontend (shm error contract).
    With a ``gate`` (continuous mode) the loop additionally publishes
    device readiness so partial batches seal against it.
    """

    _POLL_S = 0.05

    def __init__(
        self,
        engine: DetectEngine,
        batch_queue: queue.Queue,
        on_batch: Callable[[AssembledBatch, object], None],
        on_fatal: Callable[[BaseException], None],
        stop: threading.Event,
        gate: DispatchGate | None = None,
    ):
        self._engine = engine
        self._queue = batch_queue
        self._on_batch = on_batch
        self._on_fatal = on_fatal
        self._stop = stop
        self._gate = gate
        self.dispatched_batches = 0
        # watchdog: registers in _run() at thread start.
        self.thread = threading.Thread(
            target=self._run, daemon=True, name="serve-dispatch"
        )
        self.thread.start()

    def _dispatch(self, assembled: AssembledBatch):
        with trace.span(
            "serve_dispatch",
            bucket=f"{assembled.hw[0]}x{assembled.hw[1]}",
            n=len(assembled.requests),
        ):
            det = self._engine.dispatch(assembled.hw, assembled.images)
        self.dispatched_batches += 1
        if trace.enabled():
            trace.counter("serve.dispatch_qsize", self._queue.qsize())
        return det

    def _fetch(self, pending):
        assembled, det = pending
        with trace.span(
            "serve_fetch", bucket=f"{assembled.hw[0]}x{assembled.hw[1]}"
        ):
            return self._engine.fetch(det)

    def _finish(self, pending) -> None:
        self._on_batch(pending[0], self._fetch(pending))

    # Post-fetch handoff: how long the dispatcher gives an ARMED batcher
    # to seal against the just-raised gate before converting anyway.
    # Covers the batcher's armed poll (~2 ms) with margin; only ever
    # paid when slots are actually claimed.
    _HANDOFF_S = 0.02

    def _idle_flush(self, pending):
        """Queue ran dry with one batch in flight: fetch it now (overlap
        never costs latency under light load).  Continuous mode raises
        the gate the moment the results land — the assembling batch
        (claiming slots this whole round) seals against it and is
        dispatched BEFORE the fetched batch's conversion, so the device
        hop N → N+1 never waits on host-side convert work.  Returns the
        new pending batch (or None)."""
        if self._gate is None:
            self._finish(pending)
            return None
        fetched = self._fetch(pending)
        self._gate.set_ready()
        nxt = None
        try:
            if self._gate.armed():
                # Claimed slots exist: give their batcher one beat to
                # seal N+1 so it rides now, not a poll later.
                nxt = self._queue.get(timeout=self._HANDOFF_S)
            else:
                nxt = self._queue.get_nowait()
        except queue.Empty:
            pass  # still idle: the gate stays set
        if nxt is not None:
            self._gate.clear()
            det = self._dispatch(nxt)
        self._on_batch(pending[0], fetched)
        return (nxt, det) if nxt is not None else None

    def _run(self) -> None:
        # Beats on every poll (an idle dispatcher is healthy); a wedged
        # device_get — the canonical dead-device-stream hang — stops the
        # heartbeat, which is exactly what the watchdog exists to name.
        hb = watchdog.register(
            "serve-dispatch",
            details=lambda: {
                "qsize": self._queue.qsize(),
                "dispatched": self.dispatched_batches,
            },
        )
        pending = None
        try:
            while True:
                hb.beat()
                if self._stop.is_set():
                    return
                if self._gate is not None and pending is None:
                    self._gate.set_ready()  # fully idle device
                try:
                    if self._gate is not None and pending is not None:
                        # Continuous: never park a finished device round
                        # behind the poll — no queued batch means go
                        # straight to the fetch (which blocks on device
                        # compute; the gate lets the next batch seal
                        # DURING it and ride at fetch-return).
                        assembled = self._queue.get_nowait()
                    else:
                        assembled = self._queue.get(timeout=self._POLL_S)
                except queue.Empty:
                    if pending is not None:
                        pending = self._idle_flush(pending)
                    continue
                if self._gate is not None:
                    self._gate.clear()
                det = self._dispatch(assembled)
                if pending is not None:
                    self._finish(pending)
                pending = (assembled, det)
        except BaseException as exc:
            self._on_fatal(exc)
        finally:
            # A pending batch at exit needs no flush: the clean close path
            # (frontend drain) waits for in-flight == 0 BEFORE setting
            # stop (the idle-flush above fetched it), and the abort/crash
            # paths reject every outstanding future at the frontend.
            hb.close()


__all__ = [
    "DetectEngine",
    "DeviceDispatcher",
    "DispatchGate",
    "IdentityLabelMap",
    "stop_gated_put",
]
