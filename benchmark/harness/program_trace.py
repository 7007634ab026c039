"""What a traced run reads of the PROGRAM's own instrumentation.

Two things the program puts there itself, and nothing of the benchmark's:

- its ``obs/trace.py`` spans, which are profiler annotations named
  ``rn.<span>`` on the host planes of the run's ``.xplane.pb``
  (``host_spans``), on the clock the device operations are on;
- the named scopes of its train step.  The device trace names an operation
  by its HLO instruction (``fusion.1992``) and carries no scope; the
  compiled step does (``train/loop.py::compiled_step`` ->
  ``train/step.py::scope_table``), so ``slices`` joins the two by name.

No per-layer metric of ``BENCHMARK.json`` reads them yet: ``run.py`` makes a
listed metric whose reader finds nothing a fault of the run, and against a
program that has neither spans nor scopes (the parent of the PR that brought
this file) every function here returns nothing, and raises nothing.  A PR
whose parent has them lists the metrics (PERF.md section 7), each reader one
call of ``span_ms``, ``slice_ms`` or ``slice_mfu_pct``.  Until then, by hand
and on the chip, one traced run of any cell and everything this file reads
of it (``main``):

    python3 -m benchmark.harness.program_trace --workload r50-train-b8 --seed 1
"""

from __future__ import annotations

import json
import os
import time

from benchmark.harness import trace_reduce as tr

PREFIX = "rn."
MIN_COVERAGE = 0.99
UNSCOPED = "unscoped"  # as train/step.py files an instruction that bears no scope
# Slices every train step has whatever its mesh: a table without them was
# read from an executable that predates the scopes.
EXPECTED_SLICES = ("backbone", "heads", "assign", "loss", "optimizer")

_host_cache: dict[tuple[str, str], list[tr.Event]] = {}


def say(message: str) -> None:
    print("benchmark: program_trace:", message, flush=True)


# ---- the program's spans ----------------------------------------------------


def host_spans(ctx, prefix: str = PREFIX) -> list[tr.Event]:
    """The program's annotations (``rn.step``, ``rn.data_wait``,
    ``rn.device-prefetch``, ...) of this run's trace, by start time: host
    planes only, read once per process."""
    path = tr.find_xplane(ctx.run.tracer.dir) if ctx.trace is not None else None
    if path is None:
        return []
    key = (path, prefix)
    if key not in _host_cache:
        from jax.profiler import ProfileData

        t0 = time.perf_counter()
        events = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(prefix):
                            start = int(e.start_ns)
                            # kwargs ride in the name: "rn.x#bucket=64x96#"
                            events.append(tr.Event(e.name.split("#", 1)[0], start, start + int(e.duration_ns)))
        _host_cache[key] = sorted(events, key=lambda e: e.start)
        say(f"{len(events)} {prefix}* spans read off the host planes in {time.perf_counter() - t0:.2f} s")
    return _host_cache[key]


def traced_window(ctx) -> tr.Interval | None:
    """From the benchmark's window marks, NOT the steady stretch of device
    runs the device reductions read: the host dispatches a step before, often
    long before, the device runs it."""
    if ctx.trace is None:
        return None
    marks = {e.name: e for e in ctx.trace.host}
    if "bench.window_open" in marks and "bench.window_close" in marks:
        return (marks["bench.window_open"].start, marks["bench.window_close"].end)
    return ctx.window


def span_ms(ctx, name: str) -> list[float]:
    """Durations (ms) of the program's span ``name`` that began inside the
    traced window."""
    window = traced_window(ctx)
    if window is None:
        return []
    return [(e.end - e.start) / 1e6 for e in host_spans(ctx)
            if e.name == PREFIX + name and window[0] <= e.start <= window[1]]


# ---- the step's slices ------------------------------------------------------


def self_times(ops: list[tr.Event]) -> list[tuple[tr.Event, int]]:
    """Each operation with the time (ns) in which it, and no operation begun
    inside it, ran: the parts sum to the union of the intervals, so slices
    add up to ``train_step.device_ms`` whether or not operations nest."""
    out: list[list] = []
    stack: list[list] = []  # [event, self_ns, covered_until]
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        start = e.start
        if stack:
            parent = stack[-1]
            start = max(start, parent[2])  # a sibling may have run on past our start
            shared = min(e.end, parent[0].end)
            parent[1] -= max(0, shared - start)
            parent[2] = max(parent[2], shared)
        entry = [e, max(0, e.end - start), start]
        out.append(entry)
        stack.append(entry)
    return [(e, ns) for e, ns, _ in out]


def join(trace: tr.Trace, window: tr.Interval | None, module_pattern: str, table: dict,
         levels: dict | None = None) -> dict | None:
    """Device time by slice: ``table`` is ``{instruction: (slice, direction,
    path)}``; every operation inside a run of the step program inside
    ``window`` is filed under its instruction's slice, one the table lacks
    under ``unscoped``.  Returns ms per step per slice (median over runs and
    devices), the coverage (share of device time whose instruction the
    table has), and the breakdown."""
    levels = levels or {}
    per_run: list[dict[str, float]] = []
    found = missing = 0
    detail: dict[str, dict] = {}
    longest: dict[str, dict[str, list]] = {}
    missed: dict[str, int] = {}
    for d in trace.devices:
        ops = sorted(d.ops, key=lambda e: e.start)
        for m in tr.module_events(d, module_pattern, window):
            inside = [e for e in ops if e.start >= m.start and e.end <= m.end]
            run: dict[str, float] = {}
            for e, ns in self_times(inside):
                name = tr.op_name(e.name)
                entry = table.get(name)
                if entry is None:  # counted, so that the slices sum to the busy time
                    missing += ns
                    missed[name] = missed.get(name, 0) + ns
                    entry = (UNSCOPED, "fwd", "(not in the compiled step)")
                else:
                    found += ns
                slice_, direction, path = entry
                run[slice_] = run.get(slice_, 0.0) + ns / 1e6
                parts = path.split("/")
                second = next((p for p in parts[1:] if p in levels.get(slice_, ())), "-")
                cell = detail.setdefault(slice_, {}).setdefault(second, {"fwd": 0.0, "bwd": 0.0})
                cell[direction] += ns / 1e6
                op = longest.setdefault(slice_, {}).setdefault(name, [0.0, path, direction, tr.short_name(e.name, 72)])
                op[0] += ns / 1e6
            per_run.append(run)
    if not per_run or found + missing == 0:
        return None
    n = len(per_run)
    names = sorted({s for run in per_run for s in run})
    return {
        "runs": n,
        "coverage": found / (found + missing),
        "ms": {s: tr.median([run.get(s, 0.0) for run in per_run]) for s in names},
        "total_ms": tr.median([sum(run.values()) for run in per_run]),
        # mean per run, the second level and the direction beneath each slice
        "by_scope": {s: {k: {d: v / n for d, v in c.items()} for k, c in sorted(sub.items())}
                     for s, sub in sorted(detail.items())},
        "longest_ops": {s: [[name, round(ms / n, 4), direction, path, text]
                            for name, (ms, path, direction, text) in
                            sorted(ops.items(), key=lambda kv: -kv[1][0])[:5]]
                        for s, ops in sorted(longest.items())},
        "not_in_table": [[name, ns / n / 1e6] for name, ns in sorted(missed.items(), key=lambda kv: -kv[1])[:10]],
    }


def _program_table(ctx):
    """``(table, levels)`` from the program's compiled step, or nothing where
    the program cannot give one or had to compile to give it."""
    try:
        from batchai_retinanet_horovod_coco_tpu.train import loop, step

        compiled_step, scope_table, levels = loop.compiled_step, step.scope_table, step.STEP_SCOPES
    except (ImportError, AttributeError):
        return None  # a program from before the scopes
    before = ctx.run.counter.snapshot() if getattr(ctx.run, "counter", None) else None
    t0 = time.perf_counter()
    try:
        compiled = compiled_step()
    except LookupError as e:
        say(f"no compiled step: {e}")
        return None
    if before is not None:
        after = ctx.run.counter.snapshot()
        compiled_now = (after["requests"] - before["requests"]) - (after["hits"] - before["hits"])
        if compiled_now:
            say(f"compiled_step() compiled {compiled_now} program(s) instead of handing back the one "
                "that ran: its instruction names need not be the trace's; no slices")
            return None
    t1 = time.perf_counter()
    table = scope_table(compiled)
    say(f"compiled_step() in {t1 - t0:.2f} s, scope_table of {len(table)} instructions in "
        f"{time.perf_counter() - t1:.2f} s")
    absent = [s for s in EXPECTED_SLICES if s not in {t[0] for t in table.values()}]
    if absent:
        # jax leaves metadata out of the compile-cache key: an executable
        # cached before the program had its scopes comes back without them.
        say(f"the compiled step names no scope {absent}: it came out of a compile cache filled "
            "before the program had its scopes (the cache key leaves metadata out); "
            "clear the cache directory; no slices")
        return None
    return table, levels


def slices(ctx, table: dict | None = None, levels: dict | None = None) -> dict | None:
    """The step's device time by named scope in the traced run's steady
    stretch (``join``), the table written to ``<out_dir>/slices.json`` and
    printed as ``benchmark: slices {...}``; once per run.  Nothing where the
    trace has no device plane, the program gives no table, or under
    ``MIN_COVERAGE`` of the device time is found in it."""
    if hasattr(ctx, "_program_slices"):
        return ctx._program_slices
    result = None
    if ctx.trace is not None and ctx.trace.devices and ctx.window is not None:
        if table is None:
            got = _program_table(ctx)
            table, levels = got if got is not None else (None, None)
        if table is not None:
            t0 = time.perf_counter()
            result = join(ctx.trace, ctx.window, ctx.module_pattern(), table, levels)
            say(f"operations joined with the table in {time.perf_counter() - t0:.2f} s")
    if result is not None:
        with open(os.path.join(ctx.run.out_dir, "slices.json"), "w") as f:
            json.dump(result, f, indent=1)
        print("benchmark: slices", json.dumps(result), flush=True)
        if result["coverage"] < MIN_COVERAGE:
            say(f"only {result['coverage']:.4f} of the device time is in instructions the compiled "
                f"step names (first missing: {result['not_in_table'][:3]}); no slices")
            result = None
    ctx._program_slices = result
    return result


def slice_ms(ctx, name: str) -> float | None:
    s = slices(ctx)
    if s is None:
        return None
    return s["ms"].get(name, 0.0)


def slice_mfu_pct(ctx, part: str) -> float | None:
    """Model FLOP/s utilization of one slice: forward + backward convolution
    FLOPs of that part of the published architecture (``harness/flops.py``,
    3 x 2 x MACs) per step over the slice's device time, over the chip's
    published bf16 peak."""
    from benchmark.harness import flops

    ms = slice_ms(ctx, part)
    if not ms or ctx.peaks is None:
        return None
    t = ctx.run.traffic
    macs = flops.forward_macs(ctx.run.config["flops_model"], tuple(t["bucket_hw"]))[part]
    return 100.0 * 3 * 2.0 * macs * t["per_chip_batch"] / (ms / 1e3) / ctx.peaks["flops_bf16"]


# ---- by hand ------------------------------------------------------------------


def report(ctx) -> dict:
    """Everything above for one traced run, under the names the per-layer
    metrics are to have; what could not be read is left out."""
    window = traced_window(ctx)
    inside = [e for e in host_spans(ctx) if window is not None and window[0] <= e.start <= window[1]]
    out = {"spans_in_window": {n: sum(e.name == n for e in inside) for n in sorted({e.name for e in inside})},
           "train_loop.dispatch_ms": tr.median(span_ms(ctx, "step")),
           "train_loop.h2d_ms": tr.median(span_ms(ctx, "device-prefetch"))}
    s = slices(ctx)
    if s is not None:
        out["coverage"] = s["coverage"]
        out["train_step.device_ms"] = s["total_ms"]
        out.update({f"train_step.{name}_ms": ms for name, ms in s["ms"].items()})
        out.update({f"train_step.{part}_mfu_pct": slice_mfu_pct(ctx, part) for part in ("heads", "backbone")})
    return {k: v for k, v in out.items() if v is not None}


def main(argv: list[str] | None = None) -> int:
    """One traced run of a cell as ``run.py --trace 1`` makes it (the same
    set-up, warm call and measured call; no check of the outputs), then
    ``benchmark: slices {...}`` and ``benchmark: program_trace {...}``."""
    import argparse
    import shutil

    from benchmark import run as bench_run
    from benchmark.harness import device as device_lib
    from benchmark.harness import layer_context

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    _bench, run, driver = bench_run.prepare(args.workload, args.seed, args.seconds, True)
    shutil.rmtree(run.tracer.dir, ignore_errors=True)
    driver.setup()
    driver.warm()
    driver.measure()
    ctx = layer_context.build(run, driver.facts, device_lib.device_info(run.devices))
    print("benchmark: program_trace", json.dumps(report(ctx)), flush=True)
    shutil.rmtree(run.tracer.dir, ignore_errors=True)  # hundreds of MB
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
