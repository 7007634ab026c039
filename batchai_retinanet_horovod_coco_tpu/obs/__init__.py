"""Runtime observability subsystem (ISSUE 3): trace spans, structured
events, stall watchdog.

Three coordinated pieces:

- ``obs.trace`` — low-overhead spans (ring-buffered per thread, Chrome
  ``trace_event`` JSON export, cross-process merge) and the subsystem's
  ONE clock (``monotonic_s``);
- ``obs.events`` — the structured JSONL sink (run headers, metrics,
  counters/gauges, device memory);
- ``obs.watchdog`` — the heartbeat registry every long-lived thread
  registers with, and the stall diagnoser that dumps the post-mortem
  before a timeout kills the run.

Two later additions ride on those three (ISSUE 9, imported lazily by
their consumers so the core trio stays import-light):

- ``obs.telemetry`` — the live metrics registry (counters/gauges/
  windowed histograms), Prometheus text exposition, watchdog-backed
  ``healthz``, and the drain-safe HTTP status server behind
  ``train.py --obs-port`` and the serve frontend's ``GET /metrics``;
- ``obs.slo`` — the declarative SLO monitor evaluating rules on that
  registry and emitting ``slo_violation`` events/trace instants.

``enable``/``finalize`` are the run-scoped bring-up/teardown the CLI
flags (``--obs-trace``/``--obs-dir``, utils/cli.py) call; everything in
between is always-on instrumentation that costs nothing while disabled.

Import order matters for jax-free processes (shm decode workers):
``trace`` and ``watchdog`` never import jax; ``events`` only touches it
lazily.  Keep it that way — a jax import in a decode worker violates
data/shm_pipeline.py's process contract.
"""

from __future__ import annotations

import os

from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.obs import watchdog
from batchai_retinanet_horovod_coco_tpu.obs import events

__all__ = [
    "trace", "watchdog", "events", "telemetry", "slo", "numerics",
    "enable", "finalize",
]


def __getattr__(name: str):
    # Lazy submodule access (``obs.telemetry`` / ``obs.slo`` /
    # ``obs.numerics``): keeps the package's import-time surface exactly
    # the PR-3 trio for jax-free worker processes that only need
    # trace/watchdog/events (numerics imports jax at module top).
    if name in ("telemetry", "slo", "numerics"):
        import importlib

        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def enable(
    obs_dir: str,
    process_label: str = "main",
    stall_after: float = 120.0,
    sink=None,
    start_watchdog: bool = True,
) -> str:
    """Run-scoped bring-up: enable tracing into ``obs_dir`` (published to
    spawned children via the env contract) and start the stall watchdog
    (stack dumps land in ``obs_dir/watchdog_stacks.txt``)."""
    os.makedirs(obs_dir, exist_ok=True)
    trace.configure(obs_dir, process_label=process_label)
    if start_watchdog:
        watchdog.start(
            stall_after=stall_after,
            dump_path=os.path.join(obs_dir, "watchdog_stacks.txt"),
            sink=sink,
        )
    return obs_dir


def finalize() -> str | None:
    """Run-scoped teardown: export this process's trace, stop the
    watchdog, merge every per-process trace file (this process + any shm
    workers that exported on exit) into ``trace.json``.  Returns the
    merged path (None when tracing was never enabled)."""
    watchdog.stop()
    if not trace.enabled():
        return None
    trace.export()
    return trace.merge_traces()
