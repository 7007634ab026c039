"""What a run hands to its traffic kind: the cell, the seed, the devices,
and the traced run's profiler window and host annotations."""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time

now = time.perf_counter


class Tracer:
    """The traced run's device trace.  ``annotate`` wraps the benchmark's
    own calls into a layer in ``jax.profiler.TraceAnnotation`` so that they
    sit on the profiler's clock; off, it costs a null context."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.dir = os.path.join(out_dir, "xplane")
        self.opened_at = self.closed_at = None  # perf_counter

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @property
    def running(self) -> bool:
        return self.opened_at is not None and self.closed_at is None

    def start(self) -> None:
        if not self.enabled or self.opened_at is not None:
            return
        import jax

        # Device operations and host annotations only: Python call tracing
        # makes the trace large and slows the host it is measuring.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        # The first device-to-host copy after the profiler starts stalls for
        # seconds (3-5 s on a v5e, PERF.md PR 22) with the device idle.  Take
        # that stall here, on a copy of our own, before the window is marked.
        import numpy as np

        np.asarray(jax.device_put(np.zeros(1, np.float32)))
        with jax.profiler.TraceAnnotation("bench.window_open"):
            self.opened_at = now()

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        with jax.profiler.TraceAnnotation("bench.window_close"):
            self.closed_at = now()
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Run:
    cell: dict  # the workloads entry
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    seed: int
    seconds: float
    devices: list
    tracer: Tracer
    out_dir: str
    t_process: float  # perf_counter at process start
    counter: object = None  # harness.compile_counter.CompileCounter
    t_open: float | None = None
    compiles_at_open: int | None = None

    def open_window(self) -> float:
        """The traffic kind calls this at the moment its window opens:
        set-up ends here, and compilations count against the window."""
        self.t_open = now()
        self.compiles_at_open = self.counter.snapshot()["requests"]
        return self.t_open

    @property
    def chips(self) -> int:
        return len(self.devices)
