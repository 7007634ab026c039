"""What a per-layer metric's reader is given in the traced run."""

from __future__ import annotations

import dataclasses
import json
import os

from benchmark.harness import peaks as peaks_lib
from benchmark.harness import trace_reduce as tr


@dataclasses.dataclass
class LayerContext:
    run: object  # harness.runctx.Run
    facts: dict  # the traffic kind's facts about the window
    device: dict  # platform, kind, count, memory_peak_bytes
    peaks: dict | None  # published peaks of this device
    trace: tr.Trace | None  # the reduced-to-events device trace
    window: tuple[int, int] | None  # what the reductions read, trace clock (ns)
    host_spans: list[tr.Event]  # the benchmark's annotations, trace clock

    def module_pattern(self) -> str:
        return self.facts["module_pattern"]

    def device_trace_facts(self) -> dict:
        """``busy_s`` (mean over the devices) and ``window_s`` for the line's
        ``device``, from the trace alone: the union of the device-operation
        intervals inside ``window``, and its length.  The driver works out
        the idle share from the two."""
        if self.trace is None or not self.trace.devices or self.window is None:
            return {}
        b = tr.busy_and_idle(self.trace, self.window)
        return {"busy_s": b["busy_s"], "window_s": b["window_s"]}

    def breakdown(self) -> dict:
        if self.trace is None or not self.trace.devices or self.window is None:
            return {"device_ops": [], "idle_gaps": []}
        return {
            "device_ops": tr.top_ops(self.trace, self.window),
            "idle_gaps": tr.idle_gaps_by_host_activity(self.trace, self.window, self.host_spans),
        }


def build(run, facts: dict, device: dict) -> LayerContext:
    trace = window = None
    host_spans: list[tr.Event] = []
    path = tr.find_xplane(run.tracer.dir)
    if path is not None:
        trace = tr.load(path)
        marks = {e.name: e for e in trace.host}
        if "bench.window_open" in marks and "bench.window_close" in marks:
            window = (marks["bench.window_open"].start, marks["bench.window_close"].end)
        elif trace.devices:
            window = trace.span_ns()
        # The profiler disturbs what it measures (trace_reduce.quietest_stretch):
        # where the kind names its program and a number of runs, every
        # reduction reads the quietest stretch of that many consecutive runs
        # on the first device, not the whole traced stretch.
        steady = facts.get("trace_steady_runs")
        if steady and trace.devices:
            runs = tr.module_events(trace.devices[0], facts["module_pattern"], window)
            window = tr.quietest_stretch(runs, steady) or window
        host_spans += [e for e in trace.host if not e.name.startswith("bench.window")]
        summary = tr.summary(trace)
        if window is not None:
            t0 = trace.devices[0].modules[0].start if trace.devices and trace.devices[0].modules else window[0]
            summary["window_ms"] = [round((w - t0) / 1e6, 2) for w in window]
        with open(os.path.join(run.out_dir, "trace_summary.json"), "w") as f:
            json.dump(summary, f)
    kind = device["kind"]
    known = peaks_lib.PEAKS.get(kind)
    if known is None and device["platform"] == "tpu":
        peaks_lib.peaks_for(kind)  # an unknown TPU is an error, not a default
    return LayerContext(run, facts, device, known, trace, window, host_spans)
