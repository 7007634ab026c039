"""The serve frontend: admission control, request futures, stats, drain.

``DetectionServer`` wires the four serve machines together::

    submit(image) ──► admission queue (bounded; full ⇒ shed)
                        │  router workers: decode → resize → bucket
                        ▼
    per-bucket queues (bounded; full ⇒ shed)
                        │  BucketBatcher: coalesce under max_delay_ms
                        ▼
    dispatch queue (bounded; full ⇒ backpressure)
                        │  DeviceDispatcher: one-behind device dispatch
                        ▼
    fetch → detections_to_coco → per-request futures fulfilled

Contracts (pinned by tests/unit/test_serve.py):

- **Bit-identity**: a served image's detections are byte-for-byte the
  dicts ``run_coco_eval``'s sequential ``collect_detections`` produces
  for the same image — same resize (router), same batch row layout
  (batcher), same compiled program family (engine), same conversion
  (``detections_to_coco``, shared, not reimplemented).
- **Load shedding**: every queue is bounded; overload surfaces as
  ``RequestRejected(reason)`` at ``submit()`` or on the future — p99 of
  ACCEPTED requests stays bounded instead of the queue growing without
  limit.
- **Error propagation**: a crash in any serve thread fails every
  outstanding future with ``ServerError`` (original exception chained)
  and re-raises at the next ``submit()``/``result()`` — the shm
  pipeline's crash-re-raises-in-driver contract.
- **Graceful drain**: ``close()`` stops admission, waits (bounded) for
  in-flight requests to complete, then stops the threads; ``close()``
  never hangs and is idempotent.
- **Observability**: spans per stage (`serve_preprocess`,
  `serve_assemble`, `serve_dispatch`, `serve_fetch`, `serve_convert`)
  plus a cross-thread ``serve_request`` span per request; queue-depth
  counters; a watchdog heartbeat on every serve thread; periodic
  ``serve_stats`` events (p50/p99, sheds) into the obs event sink.
- **Live telemetry** (ISSUE 9): every server carries a pull-only
  metrics registry (``self.telemetry``, obs/telemetry.py — collectors
  over the same snapshot/LatencyStats the /stats payload reads, zero
  new hot-path work) exposed as ``GET /metrics`` (Prometheus text);
  ``GET /healthz`` is split from ``/stats`` and is TRUTHFUL — 503
  naming the stalled component whenever the watchdog registry reports
  a non-idle component past its stall budget — and carries the
  per-replica load fields the fleet router will weigh on.
"""

from __future__ import annotations

import itertools
import json
import queue
import threading
from typing import Any

import numpy as np

from batchai_retinanet_horovod_coco_tpu.evaluate.detect import (
    detections_to_coco,
)
from batchai_retinanet_horovod_coco_tpu.obs import telemetry, trace, watchdog
from batchai_retinanet_horovod_coco_tpu.obs.trace import monotonic_s
from batchai_retinanet_horovod_coco_tpu.serve.batcher import BucketBatcher
from batchai_retinanet_horovod_coco_tpu.serve.common import (
    AssembledBatch,
    DetectionFuture,
    LatencyStats,
    OccupancyStats,
    RequestRejected,
    RequestTimeout,
    ServeConfig,
    ServeError,
    ServeRequest,
    ServerClosed,
    ServerError,
)
from batchai_retinanet_horovod_coco_tpu.serve.engine import (
    DetectEngine,
    DeviceDispatcher,
    DispatchGate,
)
from batchai_retinanet_horovod_coco_tpu.serve.router import Router
from batchai_retinanet_horovod_coco_tpu.utils.locks import make_lock


class DetectionServer:
    """Dynamic-batching inference server over a ``DetectEngine``."""

    def __init__(
        self,
        engine: DetectEngine,
        config: ServeConfig = ServeConfig(),
        sink: Any = None,
        warmup: bool = True,
        replica_id: str | None = None,
    ):
        self.engine = engine
        self.config = config
        self.sink = sink
        # Stable identity for the fleet router / canary gate (ISSUE 12):
        # explicit (the fleet CLI pins it across restarts so the breaker
        # can re-admit "the same" replica), else host-pid — stable for
        # the server's lifetime, unique across a host's replicas.
        if replica_id is None:
            import os
            import socket

            replica_id = f"{socket.gethostname()}-{os.getpid()}"
        self.replica_id = replica_id
        self.stats = LatencyStats(window=config.latency_window)
        # The live-telemetry registry (ISSUE 9): pull-only — quantiles
        # read the LatencyStats window and the collector reads the same
        # snapshot() the /stats payload serves, all at scrape time, so
        # the request hot path pays nothing for /metrics existing.
        self.telemetry = telemetry.Registry()
        self.telemetry.histogram(
            "serve_request_latency_ms",
            "request latency over the recent window (accepted requests)",
            source=self.stats.window_ms,
        )
        # Slot-wait distribution (ISSUE 14): fed per dispatched batch in
        # _on_batch, exposed pull-only on THIS registry so both /metrics
        # surfaces carry it with no enable gating (the process-registry
        # twin, telemetry.record_serve_batch, is push-gated like the
        # train sites).
        self._slot_waits: list[float] = []
        self.telemetry.histogram(
            "serve_slot_wait_ms",
            "ms a claimed slot waited between claim and seal (continuous "
            "in-flight batching admission latency)",
            source=self._slot_wait_window,
        )
        self.telemetry.register_collector(self._telemetry_samples)
        self.telemetry.register_collector(telemetry.watchdog_collector())
        if warmup:
            engine.warmup()

        self._stop = threading.Event()
        self._lock = make_lock("serve.frontend.DetectionServer._lock")
        self._drained = threading.Condition(self._lock)
        self._outstanding: dict[int, ServeRequest] = {}
        self._error: BaseException | None = None
        self._accepting = True
        self._closed = False
        self._ids = itertools.count()
        self._batches_done = 0
        self.occupancy = OccupancyStats()

        self._admission: queue.Queue = queue.Queue(
            maxsize=max(1, config.admission_queue)
        )
        self._bucket_queues = {
            hw: queue.Queue(maxsize=max(1, config.bucket_queue))
            for hw in engine.buckets
        }
        self._dispatch_queue: queue.Queue = queue.Queue(
            maxsize=max(1, config.dispatch_depth)
        )
        # Continuous in-flight batching (ISSUE 14): the gate is the
        # device-readiness handshake partial batches seal against.
        self._gate = DispatchGate() if config.continuous else None
        self._router = Router(
            engine,
            self._admission,
            self._bucket_queues,
            on_reject=self._reject,
            on_fatal=self._fail,
            stop=self._stop,
            workers=config.preprocess_workers,
        )
        self._batchers = [
            BucketBatcher(
                hw,
                engine,
                self._bucket_queues[hw],
                self._dispatch_queue,
                config.max_delay_ms,
                on_reject=self._reject,
                on_fatal=self._fail,
                stop=self._stop,
                gate=self._gate,
            )
            for hw in engine.buckets
        ]
        self._dispatcher = DeviceDispatcher(
            engine,
            self._dispatch_queue,
            on_batch=self._on_batch,
            on_fatal=self._fail,
            stop=self._stop,
            gate=self._gate,
        )

    # ---- client surface --------------------------------------------------

    def submit(
        self,
        image,
        timeout_s: float | None = None,
        trace_id: str | None = None,
    ) -> DetectionFuture:
        """Enqueue one image (HWC uint8 array or encoded bytes); returns a
        future.  Raises ``RequestRejected`` when shed at admission,
        ``ServerClosed`` after close, ``ServerError`` after a crash.

        ``trace_id`` (ISSUE 15) parents this request's ``serve_request``
        span under a fleet-wide trace: the span's args carry it (plus the
        replica id, so a merged fleet trace attributes every request span
        to its replica even where process labels are ambiguous) and a
        flow step links it to the fleet edge's span in Perfetto."""
        self._raise_pending()
        if timeout_s is None:
            timeout_s = self.config.default_timeout_s
        req = ServeRequest(
            next(self._ids),
            image,
            None if timeout_s is None else monotonic_s() + timeout_s,
            trace_id=trace_id,
        )
        if trace_id is None:
            req.span = trace.begin(
                "serve_request", id=req.id, replica=self.replica_id
            )
        else:
            req.span = trace.begin(
                "serve_request", id=req.id, replica=self.replica_id,
                trace=trace_id,
            )
            trace.flow_step("request", trace_id)
        # The accepting check and the registration must share ONE lock
        # acquisition: close()/_fail() flip _accepting and then reject
        # everything registered, so a request registered after a lock-free
        # check could slip in after the reject sweep and never resolve.
        with self._lock:
            if not self._accepting:
                self.stats.record_shed("shutting_down")
                trace.end(req.span)
                raise ServerClosed("server is draining/closed")
            self._outstanding[req.id] = req
        try:
            self._admission.put_nowait(req)
        except queue.Full:
            exc = RequestRejected("admission_queue_full")
            self._reject(req, exc)
            raise exc from None
        if trace.enabled():
            trace.counter("serve.admission_qsize", self._admission.qsize())
        return req.future

    def detect(self, image, timeout_s: float | None = None) -> list[dict]:
        """Blocking convenience: ``submit()`` + ``result()``."""
        return self.submit(image, timeout_s=timeout_s).result()

    def snapshot(self) -> dict:
        """Stats + live queue depths (the /stats endpoint payload)."""
        snap = self.stats.snapshot()
        with self._lock:
            snap["outstanding"] = len(self._outstanding)
        snap["admission_qsize"] = self._admission.qsize()
        snap["bucket_qsize"] = {
            f"{hw[0]}x{hw[1]}": q.qsize()
            for hw, q in self._bucket_queues.items()
        }
        snap["dispatch_qsize"] = self._dispatch_queue.qsize()
        snap["batches"] = self._batches_done
        snap["deadline_fires"] = sum(b.deadline_fires for b in self._batchers)
        snap["full_fires"] = sum(b.full_fires for b in self._batchers)
        snap["ready_fires"] = sum(b.ready_fires for b in self._batchers)
        snap["slot_evictions"] = sum(b.pool.evictions for b in self._batchers)
        snap["free_slots"] = self.free_slots()
        snap["slot_capacity"] = self.slot_capacity()
        occ = self.occupancy.snapshot()
        snap["occupancy_mean"] = occ.get("mean")
        snap["occupancy_last"] = occ.get("last")
        snap["continuous"] = self.config.continuous
        return snap

    def free_slots(self) -> int:
        """Unclaimed slots across every bucket's ASSEMBLING batch — the
        idle-capacity signal the fleet router steers on (ISSUE 14)."""
        return sum(b.pool.free_slots() for b in self._batchers)

    def _slot_wait_window(self) -> list[float]:
        with self._lock:
            return list(self._slot_waits)

    def slot_capacity(self) -> int:
        return sum(b.pool.capacity for b in self._batchers)

    def _telemetry_samples(self):
        """Scrape-time collector: the snapshot() fields as Prometheus
        families (counters for lifetime totals, gauges for live depths)."""
        snap = self.snapshot()
        yield ("serve_requests_completed_total", "counter",
               "requests completed successfully", None, snap["completed"])
        yield ("serve_requests_timeout_total", "counter",
               "requests that expired past their deadline", None,
               snap["timeouts"])
        yield ("serve_requests_failed_total", "counter",
               "requests failed by a server error", None, snap["failed"])
        for reason, n in sorted(snap["shed"].items()):
            yield ("serve_shed_total", "counter",
                   "requests shed by admission control, by reason",
                   {"reason": reason}, n)
        yield ("serve_batches_total", "counter",
               "device batches dispatched", None, snap["batches"])
        yield ("serve_deadline_fires_total", "counter",
               "partial batches fired by the coalescing deadline", None,
               snap["deadline_fires"])
        yield ("serve_ready_fires_total", "counter",
               "partial batches sealed by the dispatch gate (continuous "
               "in-flight batching)", None, snap["ready_fires"])
        yield ("serve_slot_evictions_total", "counter",
               "claimed slots freed by expired-deadline eviction at the "
               "dispatch window", None, snap["slot_evictions"])
        yield ("serve_free_slots", "gauge",
               "unclaimed slots across the assembling batches (idle "
               "device capacity the fleet router steers on)", None,
               snap["free_slots"])
        if snap["occupancy_mean"] is not None:
            yield ("serve_batch_occupancy_mean", "gauge",
                   "mean live-rows/batch-size over the recent batch "
                   "window", None, snap["occupancy_mean"])
            yield ("serve_batch_occupancy_last", "gauge",
                   "live-rows/batch-size of the last dispatched batch",
                   None, snap["occupancy_last"])
        yield ("serve_inflight", "gauge",
               "requests accepted and not yet resolved", None,
               snap["outstanding"])
        yield ("serve_queue_depth", "gauge", "live queue depths",
               {"queue": "admission"}, snap["admission_qsize"])
        yield ("serve_queue_depth", "gauge", "live queue depths",
               {"queue": "dispatch"}, snap["dispatch_qsize"])
        for bucket, depth in sorted(snap["bucket_qsize"].items()):
            yield ("serve_queue_depth", "gauge", "live queue depths",
                   {"queue": f"bucket_{bucket}"}, depth)
        yield ("serve_queue_capacity", "gauge",
               "configured queue bounds (the shed thresholds)",
               {"queue": "admission"}, max(1, self.config.admission_queue))
        yield ("serve_queue_capacity", "gauge",
               "configured queue bounds (the shed thresholds)",
               {"queue": "dispatch"}, max(1, self.config.dispatch_depth))

    def load_fields(self) -> dict:
        """The per-replica load summary the /healthz payload carries —
        shaped for the serve-fleet weighted router (ROADMAP): in-flight,
        queue depths vs bounds, and the windowed p99."""
        snap = self.snapshot()
        return {
            # Identity first (ISSUE 12): without these the fleet router
            # cannot attribute health, and the canary gate cannot tell
            # which export version a p99 regression belongs to.
            "replica_id": self.replica_id,
            "version": getattr(self.engine, "version", "live"),
            "inflight": snap["outstanding"],
            "admission_qsize": snap["admission_qsize"],
            "admission_capacity": max(1, self.config.admission_queue),
            "dispatch_qsize": snap["dispatch_qsize"],
            "bucket_qsize": snap["bucket_qsize"],
            "p99_ms": snap.get("p99_ms"),
            "completed": snap["completed"],
            "shed_total": snap["shed_total"],
            # Occupancy signals (ISSUE 14): free slots in the assembling
            # batches + recent mean batch occupancy — the fleet router
            # folds these into its weights so load steers at replicas
            # with idle device capacity.
            "free_slots": snap["free_slots"],
            "slot_capacity": snap["slot_capacity"],
            "occupancy": snap["occupancy_mean"],
            "accepting": self._accepting,
        }

    def close(self, drain: bool = True, timeout_s: float | None = None) -> None:
        """Stop accepting, optionally drain in-flight work, stop threads.

        Never hangs: the drain wait is bounded (``config.drain_timeout_s``
        unless overridden) and leftovers are rejected with
        ``ServerClosed``; idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._accepting = False
        if drain and self._error is None:
            budget = (
                self.config.drain_timeout_s if timeout_s is None else timeout_s
            )
            deadline = monotonic_s() + budget
            with self._drained:
                while self._outstanding:
                    remaining = deadline - monotonic_s()
                    if remaining <= 0:
                        break
                    self._drained.wait(timeout=min(remaining, 0.2))
        self._stop.set()
        self._reject_all(ServerClosed("server closed"))
        for t in (
            *self._router.threads,
            *(b.thread for b in self._batchers),
            self._dispatcher.thread,
        ):
            t.join(timeout=10)
        self._emit_stats(final=True)

    def __enter__(self) -> "DetectionServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))

    # ---- completion paths (any serve thread) -----------------------------

    def _finish(self, req: ServeRequest, *, result=None, error=None) -> bool:
        """Complete one request exactly once (both the fulfill and reject
        paths funnel here); False if it was already completed."""
        with self._lock:
            if self._outstanding.pop(req.id, None) is None:
                return False
            self._drained.notify_all()
        trace.end(req.span)
        if error is None:
            self.stats.record(monotonic_s() - req.t_submit)
            req.future._set_result(result)
        else:
            if isinstance(error, RequestRejected):
                self.stats.record_shed(error.reason)
            elif isinstance(error, RequestTimeout):
                self.stats.record_timeout()
            else:
                self.stats.record_failure()
            req.future._set_error(error)
        return True

    def _reject(self, req: ServeRequest, exc: BaseException) -> None:
        self._finish(req, error=exc)

    def _reject_all(self, exc: BaseException) -> None:
        with self._lock:
            pending = list(self._outstanding.values())
        for req in pending:
            self._finish(req, error=exc)

    def _fail(self, exc: BaseException) -> None:
        """Fatal error in any serve thread: record once, stop everything,
        fail every outstanding future (shm-pipeline crash contract)."""
        with self._lock:
            if self._error is None:
                self._error = exc
            self._accepting = False
        self._stop.set()
        wrapped = ServerError("serve worker thread crashed")
        wrapped.__cause__ = exc
        self._reject_all(wrapped)

    def _raise_pending(self) -> None:
        if self._error is not None:
            raise ServerError("serve worker thread crashed") from self._error

    # ---- batch completion (dispatcher thread) ----------------------------

    def _on_batch(self, assembled: AssembledBatch, det) -> None:
        reqs = assembled.requests
        n = assembled.images.shape[0]
        with trace.span(
            "serve_convert",
            bucket=f"{assembled.hw[0]}x{assembled.hw[1]}",
            n=len(reqs),
        ):
            # Per-row completion release (ISSUE 14): de-pad, convert, and
            # resolve ROW BY ROW — an early row's future resolves without
            # waiting on its bucket siblings' conversion.  The conversion
            # IS the eval path's ``detections_to_coco`` (rescale to
            # original coords, clamp to true bounds, drop degenerates),
            # called on single-row views; it is strictly per-row math, so
            # the slicing cannot change any result (PARITY §5.9).  Pad
            # rows (beyond len(reqs)) never convert at all.
            for i, req in enumerate(reqs):
                row = type(det)(
                    det.boxes[i:i + 1], det.scores[i:i + 1],
                    det.labels[i:i + 1], det.valid[i:i + 1],
                )
                dets = detections_to_coco(
                    row,
                    np.array([req.id], dtype=np.int64),
                    assembled.scales[i:i + 1],
                    assembled.valid[i:i + 1],
                    self.engine.label_to_cat_id,
                    image_sizes={req.id: req.orig_wh},
                )
                for d in dets:
                    d.pop("image_id", None)  # request-scoped; transport
                if req.expired():
                    self._finish(req, error=RequestTimeout(
                        f"request {req.id} finished after its deadline"
                    ))
                else:
                    self._finish(req, result=dets)
        self._batches_done += 1
        self.occupancy.record(len(reqs) / max(1, n))
        if assembled.slot_wait_ms:
            with self._lock:
                self._slot_waits.extend(assembled.slot_wait_ms)
                if len(self._slot_waits) > 4096:
                    del self._slot_waits[:-4096]
        if telemetry.enabled():
            # Args computed only on the enabled path: free_slots() takes
            # one lock per bucket pool — not a price the disabled hot
            # path pays (the callee's own gate is the second check).
            telemetry.record_serve_batch(
                occupancy=len(reqs) / max(1, n),
                free_slots=self.free_slots(),
                slot_wait_ms=assembled.slot_wait_ms,
            )
        if (
            self.sink is not None
            and self._batches_done % max(1, self.config.stats_every_batches)
            == 0
        ):
            self._emit_stats()

    def _emit_stats(self, final: bool = False) -> None:
        if self.sink is None:
            return
        try:
            self.sink.event(
                "serve_stats", final=final, **_flatten(self.snapshot())
            )
            # The full latency distribution record (p50/p90/p99/max over
            # the raw window) rides along for richer offline analysis.
            self.sink.histogram(
                "serve.request_latency", self.stats.window_ms()
            )
        except Exception:
            pass  # stats must never take the serving path down


def _flatten(snap: dict) -> dict:
    """Nested snapshot → JSONL-friendly flat fields."""
    out = {}
    for k, v in snap.items():
        if isinstance(v, dict):
            for kk, vv in v.items():
                out[f"{k}.{kk}"] = vv
        else:
            out[k] = v
    return out


# ---- stdlib HTTP frontend ------------------------------------------------


def serve_http(
    server: DetectionServer,
    host: str = "127.0.0.1",
    port: int = 0,
    request_timeout_s: float = 60.0,
    stream=None,
):
    """Wrap a ``DetectionServer`` in a stdlib ``ThreadingHTTPServer``.

    POST /detect   (body = encoded image)  → 200 JSON detections,
                   503 + reason on shed, 504 on deadline, 500 on crash
    POST /stream/open   (JSON {width?, height?}) → 200 {session, bucket}
    POST /stream/frame  (headers X-Retinanet-Stream + X-Retinanet-Frame,
                   optional X-Retinanet-Deadline-Ms; body = encoded
                   frame) → 200 {detections (with track_id), frame,
                   cache_hit}; 404 unknown session, 400 out-of-order /
                   bad input, 503 backlogged/shed, 504 deadline
                   (serve/stream.py — ISSUE 18)
    POST /stream/close  (header X-Retinanet-Stream) → 200 final stats
    GET  /stream   → 200 JSON per-stream status snapshot
    GET  /stats    → 200 JSON stats snapshot
    GET  /metrics  → 200 Prometheus text exposition (server.telemetry)
    GET  /healthz  → TRUTHFUL liveness, split from /stats (ISSUE 9
                   satellite — it used to be a cosmetic alias): 200 +
                   per-replica load fields while every watchdog
                   component is within budget, 503 naming the stalled
                   component otherwise (read-only probe; the watchdog
                   poll thread keeps its one-dump-per-stall latch)

    Request tracing (ISSUE 15): an ``X-Retinanet-Trace`` request header
    (minted here when absent) parents the request's ``serve_request``
    span; EVERY /detect response — success, shed, timeout, crash —
    echoes it back as the same header plus a ``trace_id`` JSON field, so
    a client or bench log can correlate a slow response with its span in
    the merged fleet trace.

    ``request_timeout_s`` bounds each handler's wait on its future — an
    HTTP client must never hang on a wedged pipeline (the watchdog names
    the wedge; the client gets a 504).  Returns the ``http.server``
    instance; the caller owns ``serve_forever()`` / ``shutdown()`` (the
    CLI below runs it).  The stream manager is created lazily on first
    streaming use and closed by ``server_close()``, so callers need no
    extra teardown step.
    """
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    # Streaming sessions ride the same frontend, but the manager (and
    # its delivery thread) is created lazily on the first /stream*
    # request: image-only servers never pay for it, and every existing
    # ``shutdown(); server_close()`` teardown stays leak-free because
    # ``server_close`` below also closes the manager if one was made.
    _stream_lock = make_lock("serve.frontend.serve_http._stream_lock")
    _stream_holder = [stream]

    def _stream():
        with _stream_lock:
            if _stream_holder[0] is None:
                from batchai_retinanet_horovod_coco_tpu.serve.stream import (
                    StreamManager,
                )

                _stream_holder[0] = StreamManager(server)
            return _stream_holder[0]

    class Handler(BaseHTTPRequestHandler):
        def _json(
            self, code: int, payload: dict, trace_id: str | None = None
        ) -> None:
            if trace_id is not None:
                payload = {**payload, "trace_id": trace_id}
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            if trace_id is not None:
                self.send_header(trace.TRACE_HEADER, trace_id)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802 (stdlib casing)
            if self.path == "/stats":
                self._json(200, server.snapshot())
            elif self.path == "/healthz":
                code, payload = telemetry.healthz()
                payload["load"] = server.load_fields()
                self._json(code, payload)
            elif self.path == "/stream":
                self._json(200, _stream().status())
            elif self.path == "/metrics":
                body = server.telemetry.prometheus_text().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._json(404, {"error": "not_found"})

        def _stream_rejected(self, exc, trace_id):
            """The stream flavor of the taxonomy → status-code mapping:
            a dead/unknown session is 404 (re-open, don't retry), client
            protocol faults (bad input, out-of-order frame) are 400,
            everything transient is 503."""
            if exc.reason == "unknown_stream":
                code = 404
            elif exc.reason in ("decode_error", "stream_out_of_order"):
                code = 400
            else:
                code = 503
            self._json(
                code, {"error": "rejected", "reason": exc.reason},
                trace_id=trace_id,
            )

        def _do_stream(self, trace_id):
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length)
            try:
                if self.path == "/stream/open":
                    spec = json.loads(body) if body else {}
                    out = _stream().open_stream(
                        width=spec.get("width"),
                        height=spec.get("height"),
                        trace_id=trace_id,
                    )
                    self._json(200, out, trace_id=trace_id)
                elif self.path == "/stream/frame":
                    sid = self.headers.get("X-Retinanet-Stream", "")
                    try:
                        seq = int(self.headers.get("X-Retinanet-Frame", -1))
                        deadline_ms = self.headers.get(
                            "X-Retinanet-Deadline-Ms"
                        )
                        timeout_s = (
                            float(deadline_ms) / 1e3
                            if deadline_ms else None
                        )
                    except ValueError:
                        # A malformed header is the client's fault: 400
                        # via the taxonomy mapping, not a dropped
                        # connection.
                        raise RequestRejected(
                            "decode_error", "malformed stream header"
                        ) from None
                    fut = _stream().submit_frame(
                        sid, seq, body,
                        timeout_s=timeout_s,
                        trace_id=trace_id,
                    )
                    dets = fut.result(timeout=request_timeout_s)
                    self._json(
                        200,
                        {
                            "detections": dets,
                            "frame": seq,
                            "cache_hit": bool(
                                getattr(fut, "cache_hit", False)
                            ),
                        },
                        trace_id=trace_id,
                    )
                elif self.path == "/stream/close":
                    sid = self.headers.get("X-Retinanet-Stream", "")
                    stats = _stream().close_stream(sid)
                    self._json(
                        200, {"closed": sid, "stats": stats},
                        trace_id=trace_id,
                    )
                else:
                    self._json(404, {"error": "not_found"})
            except RequestRejected as exc:
                self._stream_rejected(exc, trace_id)
            except (RequestTimeout, TimeoutError):
                self._json(
                    504, {"error": "deadline_exceeded"}, trace_id=trace_id
                )
            except ServeError as exc:
                self._json(
                    500, {"error": "server_error", "detail": str(exc)},
                    trace_id=trace_id,
                )
            except Exception as exc:
                # Same catch-all the fleet frontend carries: an
                # unexpected handler fault answers 500 instead of
                # closing the connection mid-request.
                self._json(
                    500, {"error": "server_error", "detail": str(exc)},
                    trace_id=trace_id,
                )

        def do_POST(self):  # noqa: N802
            if self.path.startswith("/stream/"):
                trace_id = (
                    self.headers.get(trace.TRACE_HEADER)
                    or trace.new_trace_id()
                )
                self._do_stream(trace_id)
                return
            if self.path != "/detect":
                self._json(404, {"error": "not_found"})
                return
            # The propagated fleet trace id (minted here for direct
            # clients) — every response branch echoes it (ISSUE 15).
            trace_id = (
                self.headers.get(trace.TRACE_HEADER) or trace.new_trace_id()
            )
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length)
            try:
                dets = server.submit(body, trace_id=trace_id).result(
                    timeout=request_timeout_s
                )
            except RequestRejected as exc:
                # The taxonomy distinction in status codes: a bad INPUT is
                # the client's fault and not retryable (400); shed load is
                # transient and retryable (503).
                code = 400 if exc.reason == "decode_error" else 503
                self._json(
                    code, {"error": "rejected", "reason": exc.reason},
                    trace_id=trace_id,
                )
            except (RequestTimeout, TimeoutError):
                self._json(
                    504, {"error": "deadline_exceeded"}, trace_id=trace_id
                )
            except ServeError as exc:
                self._json(
                    500, {"error": "server_error", "detail": str(exc)},
                    trace_id=trace_id,
                )
            else:
                self._json(200, {"detections": dets}, trace_id=trace_id)

        def log_message(self, *args) -> None:
            pass  # request logging is the stats/obs layer's job

    class _ServeHTTPServer(ThreadingHTTPServer):
        # ``stream_manager`` creates on first touch (same lazy path the
        # handlers use); ``server_close`` tears down whatever exists so
        # the standard ``shutdown(); server_close()`` teardown never
        # leaks the delivery thread.
        @property
        def stream_manager(self):
            return _stream()

        def server_close(self):
            with _stream_lock:
                mgr = _stream_holder[0]
            if mgr is not None:
                mgr.close()
            super().server_close()

    return _ServeHTTPServer((host, port), Handler)


# ---- CLI -----------------------------------------------------------------


def build_parser():
    import argparse

    from batchai_retinanet_horovod_coco_tpu.utils.cli import (
        add_obs_flags,
        add_serve_flags,
    )

    p = argparse.ArgumentParser(
        description="Serve an exported detector (convert_model.py output) "
                    "over HTTP, or run it over a directory of images.",
    )
    p.add_argument("--export-dir", default=None,
                   help="export directory (manifest.json + .stablehlo "
                        "artifacts) from convert_model.py; required "
                        "unless --stub-engine")
    p.add_argument("--stub-engine", action="store_true",
                   help="serve the stub engine instead of an export: no "
                        "device work, one fixed detection per request — "
                        "the fleet smoke / chaos harness replica "
                        "(serve/stub.py)")
    p.add_argument("--stub-delay-ms", type=float, default=0.0,
                   help="stub engine per-dispatch delay (simulated "
                        "device time; lets harnesses shape p99)")
    p.add_argument("--stub-video", action="store_true",
                   help="stub engine video mode (ISSUE 18): each row's "
                        "boxes derive from that row's pixel brightness, "
                        "so seeded drift footage yields deterministic "
                        "drifting boxes — the streaming smoke/tests "
                        "replica")
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--http", type=int, metavar="PORT",
                      help="start the HTTP frontend on this port "
                           "(0 = ephemeral; serves until interrupted)")
    mode.add_argument("--images", metavar="DIR",
                      help="offline mode: submit every image in DIR, "
                           "write detections JSONL, print stats, exit")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--output", default=None,
                   help="offline mode: detections JSONL path "
                        "(default: stdout summary only)")
    p.add_argument("--platform", default="auto",
                   choices=["auto", "cpu", "tpu"],
                   help="backend to serve on (same flag surface as "
                        "convert_model.py / train.py)")
    add_serve_flags(p)
    add_obs_flags(p)
    return p


def main(argv: list[str] | None = None) -> dict:
    import os
    import signal

    args = build_parser().parse_args(argv)

    if args.platform != "auto":
        import jax

        jax.config.update("jax_platforms", args.platform)

    from batchai_retinanet_horovod_coco_tpu.utils.cli import (
        configure_obs,
        make_serve_config,
    )

    # Replica-labeled process track in the merged fleet trace (ISSUE 15):
    # the per-process trace file and its Perfetto process group carry the
    # replica id, not a generic "serve".
    process_label = getattr(args, "replica_id", None) or "serve"
    obs_dir = configure_obs(args, process_label=process_label)
    # Fleet-spawned replicas join the parent's RETINANET_OBS_DIR export
    # contract (the shm-worker mechanism): tracing self-enables under the
    # parent's run id, this process exports its own trace fragment at
    # exit, and the fleet CLI's finalize merges it onto the fleet
    # timeline.  Explicit --obs-trace/--obs-dir flags win.
    joined_env = obs_dir is None and trace.maybe_configure_from_env(
        process_label
    )
    # The fleet CLI stops replicas with SIGTERM: exit through the same
    # finally as an interrupt so the trace fragment is exported and the
    # server drains instead of dying mid-request.
    def _on_sigterm(_signum, _frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_sigterm)
    if args.stub_engine:
        from batchai_retinanet_horovod_coco_tpu.serve.stub import (
            StubDetectEngine,
        )

        engine = StubDetectEngine(
            delay_s=args.stub_delay_ms / 1e3, video=args.stub_video
        )
    elif args.export_dir is None:
        raise SystemExit("--export-dir is required (or pass --stub-engine)")
    else:
        # Only a replica that serves an export touches the device; stub
        # replicas stay backend-free (N of them share a host with the
        # one process that owns the chip).
        from batchai_retinanet_horovod_coco_tpu.utils.backend import (
            announce_devices,
            enable_compile_cache,
        )

        enable_compile_cache()
        announce_devices("serve")
        engine = DetectEngine.from_export(args.export_dir)
    print(
        f"engine: buckets={engine.buckets} "
        f"batch_sizes={ {hw: engine.batch_sizes(hw) for hw in engine.buckets} } "
        f"resize={engine.min_side}/{engine.max_side} "
        f"version={getattr(engine, 'version', 'live')}"
    )
    sink = None
    if obs_dir is not None:
        # serve_stats / watchdog_stall / slo_violation events land in
        # metrics.jsonl next to the trace (the perf doctor's events half).
        from batchai_retinanet_horovod_coco_tpu.obs.events import EventSink

        sink = EventSink(obs_dir, run_config=vars(args))
        watchdog.default().sink = sink
    server = DetectionServer(
        engine, make_serve_config(args), sink=sink,
        replica_id=getattr(args, "replica_id", None),
    )
    slo_monitor = None
    status_server = None
    try:
        # Telemetry/SLO bring-up INSIDE the try: a typo'd --slo-rule or
        # an already-bound --obs-port must still drain the server and
        # close the sink on the way out.  Same policy as train.py's
        # _start_telemetry: either flag starts the monitor (the built-in
        # stall rule is always included).
        if (
            obs_dir is not None
            or getattr(args, "slo_rule", None)
            or getattr(args, "obs_port", None) is not None
        ):
            # Arm the push-path record sites (telemetry.record_serve_batch
            # → the process default registry) whenever observability is
            # on — the same policy as train.py's _start_telemetry.
            telemetry.enable()
        if (
            getattr(args, "slo_rule", None)
            or getattr(args, "obs_port", None) is not None
        ):
            from batchai_retinanet_horovod_coco_tpu.obs import slo as slo_lib

            slo_monitor = slo_lib.SloMonitor(
                server.telemetry,
                [slo_lib.stall_rule()]
                + [slo_lib.parse_rule(s) for s in (args.slo_rule or [])],
                sink=sink,
                poll_interval=args.slo_poll_s,
            ).start()
        if getattr(args, "obs_port", None) is not None:
            # A second, serve-path-independent scrape port (the offline
            # --images mode has no HTTP frontend; on --http it lets the
            # scraper live apart from request traffic).
            status_server = telemetry.start_http_server(
                server.telemetry, port=args.obs_port, host=args.host
            )
            print(
                f"telemetry on http://{status_server.host}:"
                f"{status_server.port} (/metrics /healthz /statusz)"
            )
        if args.images is not None:
            names = sorted(
                n for n in os.listdir(args.images)
                if n.lower().endswith((".jpg", ".jpeg", ".png", ".bmp"))
            )
            # The offline client is a polite one: on an admission shed it
            # BLOCKS on its oldest in-flight future and retries, instead
            # of crashing — a directory larger than the admission queue
            # must drain completely, not trip the overload protection.
            futures: list[tuple[str, object]] = []
            drained = 0
            records = []

            def drain_one():
                nonlocal drained
                name, fut = futures[drained]
                drained += 1
                try:
                    records.append({"file": name, "detections": fut.result()})
                except ServeError as exc:
                    records.append({"file": name, "error": str(exc)})

            for name in names:
                with open(os.path.join(args.images, name), "rb") as f:
                    payload = f.read()
                while True:
                    try:
                        futures.append((name, server.submit(payload)))
                        break
                    except RequestRejected:
                        if drained >= len(futures):
                            raise  # nothing in flight to wait on
                        drain_one()
            while drained < len(futures):
                drain_one()
            if args.output:
                # Atomic: downstream tooling ingests this JSONL by name;
                # publish it complete or not at all — streamed, so a big
                # offline batch never materializes twice in memory.
                from batchai_retinanet_horovod_coco_tpu.utils.atomicio import (
                    atomic_writer,
                )

                with atomic_writer(args.output) as f:
                    for rec in records:
                        f.write(json.dumps(rec) + "\n")
                print(f"wrote {len(records)} records to {args.output}")
        else:
            httpd = serve_http(server, args.host, args.http)
            print(
                f"serving on http://{httpd.server_address[0]}:"
                f"{httpd.server_address[1]} (POST /detect /stream/*; "
                "GET /stats /stream /metrics /healthz)"
            )
            try:
                httpd.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                httpd.shutdown()
                httpd.server_close()  # also closes the stream manager
        snap = server.snapshot()
        print(json.dumps({"serve_stats": snap}))
        return snap
    finally:
        if slo_monitor is not None:
            slo_monitor.stop()
        if status_server is not None:
            status_server.close()
        server.close()
        if sink is not None:
            sink.close()
        if obs_dir is not None:
            from batchai_retinanet_horovod_coco_tpu import obs

            obs.finalize()
        elif joined_env:
            # Env-joined (fleet-spawned) replica: export THIS process's
            # fragment only — the fleet parent owns the merge.
            trace.export()


if __name__ == "__main__":
    main()
