"""From the profiler's ``.xplane.pb`` to numbers.

The JAX profiler writes one plane per device (``/device:TPU:<n>``) and one
for the host.  A device plane has a line of XLA operations ("XLA Ops", one
event per executed HLO operation) and a line of whole programs ("XLA
Modules", one event per executed program).  Everything here is interval
arithmetic over those events, in nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
# An operation's event is named by its whole HLO text,
# "%all-reduce-start.1 = (f32[...]) all-reduce-start(...)": the instruction's
# own name is the part before " = ".
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)"
)


def op_name(event_name: str) -> str:
    """The HLO instruction's own name: ``fusion.1992``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def short_name(event_name: str, width: int = 96) -> str:
    """Name, result shape and kind, cut to ``width``: what a breakdown shows."""
    head, _, rest = event_name.partition(" = ")
    return (head.lstrip("%") + " " + rest)[:width].rstrip()

Interval = tuple[int, int]


# ---- interval arithmetic (pure) -------------------------------------------


def union(intervals: list[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: list[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: list[Interval], lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Parts of ``a`` (a union) that ``b`` (a union) does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: list[Interval], lo: int, hi: int) -> list[Interval]:
    return subtract([(lo, hi)], clip(busy, lo, hi))


def median(values):
    v = sorted(values)
    if not v:
        return None
    m = len(v) // 2
    return v[m] if len(v) % 2 else 0.5 * (v[m - 1] + v[m])


# ---- the trace -------------------------------------------------------------


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: list[Event]  # the TensorCore's operations, one after another
    modules: list[Event]  # whole programs
    async_ops: list[Event] = dataclasses.field(default_factory=list)  # DMA and collectives in flight

    def busy(self) -> list[Interval]:
        return union([(e.start, e.end) for e in self.ops])


@dataclasses.dataclass
class Trace:
    devices: list[DevicePlane]
    host: list[Event]  # TraceAnnotation / TraceMe events on host threads

    def span_ns(self) -> Interval:
        starts = [e.start for d in self.devices for e in d.ops]
        ends = [e.end for d in self.devices for e in d.ops]
        return (min(starts), max(ends)) if starts else (0, 0)


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, host_prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb``; of the host's events keep those whose name
    starts with ``host_prefix`` (the benchmark's own annotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, modules, async_ops = [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [_event(e) for e in line.events]
                elif line.name == ASYNC_LINE:
                    async_ops = [_event(e) for e in line.events]
            if ops or modules:
                devices.append(DevicePlane(plane.name, ops, modules, async_ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append(_event(e))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


def _event(e) -> Event:
    start = int(e.start_ns)
    return Event(e.name, start, start + int(e.duration_ns))


# ---- reductions ------------------------------------------------------------


def busy_and_idle(trace: Trace, window: Interval) -> dict:
    """Busy seconds averaged over the devices, the window's length, and the
    idle share of the idlest device."""
    lo, hi = window
    busy = [total(clip(d.busy(), lo, hi)) for d in trace.devices]
    length = hi - lo
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": length / 1e9,
        "idle_share_worst": 1.0 - min(busy) / length,
        "per_device_busy_s": [b / 1e9 for b in busy],
    }


def module_events(device: DevicePlane, pattern: str, window: Interval | None = None) -> list[Event]:
    rx = re.compile(pattern)
    out = [m for m in device.modules if rx.search(m.name)]
    if window is not None:
        out = [m for m in out if m.start >= window[0] and m.end <= window[1]]
    return out


def quietest_stretch(runs: list[Event], n: int) -> Interval | None:
    """Of every ``n`` consecutive program runs, the stretch (first start to
    last end) that took least time.  While the profiler runs, a device-to-host
    copy stalls for about a second and starting it for 3-9 s (PERF.md, PR 22):
    a stretch between such stalls shows the program, any other the profiler.
    A host that cannot keep up leaves a gap before every run, so no stretch
    is free of it and the idle share shows it; a bubble that comes once in
    more than ``n`` runs (the loop's scalar fetch) is not seen."""
    runs = sorted(runs, key=lambda e: e.start)
    if n < 1 or len(runs) < n:
        return None
    first = min(range(len(runs) - n + 1), key=lambda i: runs[i + n - 1].end - runs[i].start)
    return (runs[first].start, runs[first + n - 1].end)


def per_module_busy_ms(trace: Trace, pattern: str, window: Interval | None = None) -> list[float]:
    """For every run of a program whose name matches, on every device, the
    time in which an operation ran inside it (ms)."""
    out = []
    for d in trace.devices:
        busy = d.busy()
        for m in module_events(d, pattern, window):
            out.append(total(clip(busy, m.start, m.end)) / 1e6)
    return out


def op_time_per_module_ms(trace: Trace, op_pattern: str, module_pattern: str,
                          window: Interval | None = None) -> list[float]:
    """Summed duration of the matching operations inside each run of the
    matching program (ms), over all devices."""
    rx = re.compile(op_pattern)
    out = []
    for d in trace.devices:
        ops = sorted((e for e in d.ops if rx.search(op_name(e.name))), key=lambda e: e.start)
        for m in module_events(d, module_pattern, window):
            out.append(sum(e.end - e.start for e in ops if e.start >= m.start and e.end <= m.end) / 1e6)
    return out


def collectives_per_module_ms(trace: Trace, module_pattern: str,
                              window: Interval | None = None) -> dict:
    """Per run of the matching program: time covered by collective
    operations, and the part of it in which no other operation ran on that
    device (exposed).  An asynchronous pair counts from the start of
    ``-start`` to the end of ``-done``."""
    totals, exposed = [], []
    for d in trace.devices:
        for m in module_events(d, module_pattern, window):
            # Compute is what the TensorCore's line shows; of the operations in
            # flight beside it only collectives count.
            candidates = d.ops + [e for e in d.async_ops if COLLECTIVE.match(op_name(e.name))]
            inside = [e for e in candidates if e.start >= m.start and e.end <= m.end]
            t, x = collective_split(inside)
            totals.append(t / 1e6)
            exposed.append(x / 1e6)
    return {"total_ms": totals, "exposed_ms": exposed}


def collective_split(ops: list[Event]) -> tuple[int, int]:
    coll: list[Interval] = []
    compute: list[Interval] = []
    open_starts: dict[str, int] = {}
    for e in sorted(ops, key=lambda e: e.start):
        name = op_name(e.name)
        found = COLLECTIVE.match(name)
        if not found:
            if not name.startswith(("copy-start", "copy-done", "slice-start", "slice-done")):
                compute.append((e.start, e.end))
            continue
        base = found.group(1)
        if "-start" in name:
            open_starts[base + name.split("-start")[-1]] = e.start
        elif "-done" in name:
            coll.append((open_starts.pop(base + name.split("-done")[-1], e.start), e.end))
        else:
            coll.append((e.start, e.end))
    cu = union(coll)
    return total(cu), total(subtract(cu, union(compute)))


def top_ops(trace: Trace, window: Interval, n: int = 10) -> list[list]:
    """Device operations that took most time (seconds, mean over devices)."""
    lo, hi = window
    acc: dict[str, int] = {}
    for d in trace.devices:
        for e in d.ops:
            if e.end > lo and e.start < hi:
                name = short_name(e.name)
                acc[name] = acc.get(name, 0) + (min(e.end, hi) - max(e.start, lo))
    k = max(1, len(trace.devices))
    return [[name, ns / k / 1e9] for name, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_host_activity(trace: Trace, window: Interval, host_spans: list[Event],
                               n: int = 10, min_gap_ns: int = 20_000) -> list[list]:
    """The idle time of the first device, attributed: each gap of at least
    ``min_gap_ns`` goes to the host span (the benchmark's annotations, on
    the trace's clock) that overlaps it most, the shorter span winning a
    tie; shorter gaps are summed under one name."""
    if not trace.devices:
        return []
    lo, hi = window
    acc: dict[str, int] = {}
    spans = sorted(host_spans, key=lambda e: e.start)
    active: list[Event] = []
    nxt = 0
    for s, e in gaps(trace.devices[0].busy(), lo, hi):
        if e - s < min_gap_ns:
            acc["between_ops_short"] = acc.get("between_ops_short", 0) + (e - s)
            continue
        while nxt < len(spans) and spans[nxt].start < e:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp.end > s]
        best, key = "host:unattributed", (0, 0)
        for sp in active:
            overlap = min(e, sp.end) - max(s, sp.start)
            k = (overlap, -(sp.end - sp.start))
            if overlap > 0 and k > key:
                best, key = sp.name, k
        acc[best] = acc.get(best, 0) + (e - s)
    return [[name, ns / 1e9] for name, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def summary(trace: Trace) -> dict:
    """What a person looks at first: planes, counts, the commonest names."""
    out = {"devices": [], "host_annotations": sorted({e.name for e in trace.host})}
    for d in trace.devices:
        names: dict[str, int] = {}
        for m in d.modules:
            names[m.name] = names.get(m.name, 0) + 1
        t0 = d.modules[0].start if d.modules else 0
        out["devices"].append({
            "plane": d.name, "ops": len(d.ops), "async_ops": len(d.async_ops), "modules": names,
            # [start, length] in ms of each program run, from the first one
            "timeline_ms": [[round((m.start - t0) / 1e6, 2), round((m.end - m.start) / 1e6, 2)]
                            for m in d.modules[:80]],
        })
    return out
