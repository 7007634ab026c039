"""What a traced run reads of the PROGRAM's own record of its set-up.

The program keeps its set-up as phases (``obs/trace.py::phases``, ISSUE 34):
``(id, parent, name, t0, dur, args, thread)`` on ``time.perf_counter``, the
clock ``Run.t_open`` is on.  Its own boundaries (``init_state``,
``place_state``, ``compile_train_step`` with ``bucket`` and ``call``) and,
beneath them, what JAX reported of every program it built: ``jit_trace``,
``jit_lower`` and ``xla_compile_or_load`` with ``fun``, ``cache`` (``hit``,
``miss`` or ``off``) and ``retrieval_s``.  The readers import that record in
the process that ran the cell, as ``program_trace._program_table`` imports
``loop.compiled_step``; everything before the window opened is set-up.

``run.py`` makes "listed for this cell and its reader found nothing" a fault
of the run, and the driver makes every traced run on the parent's program
too.  So the rule of what a reader returns lives here, once (``values``):

(a) a program that offers no phase record at all accounted for nothing:
    every metric reads ``0.0`` and one line says why;
(b) a program that offers the record, but holds no ``compile_train_step``
    before the window or no JAX phase beneath any of them, has a BROKEN
    record: every metric reads ``None`` and the run is not correct, by the
    metric's name;
(c) a sum over no matching phase inside a sound record is ``0.0``.

By hand and on the chip, one traced run of any cell with the eight values
and the phase table (``main``):

    python3 -m benchmark.harness.setup_phases --workload r50-train-b8 --seed 1
"""

from __future__ import annotations

import json

METRICS = ("setup.trace_lower_s", "setup.cache_load_s", "setup.backend_compile_s", "setup.cache_misses",
           "setup.step_builds", "setup.step_build_s", "setup.init_state_s", "setup.place_state_s")
STEP_BUILD = "compile_train_step"
JAX_PHASES = ("jit_trace", "jit_lower", "xla_compile_or_load")
NO_RECORD = "this program keeps no phase record; the set-up metrics read 0"


def say(message: str) -> None:
    print("benchmark: setup_phases:", message, flush=True)


def program_phases() -> list | None:
    """The program's filed phases, or nothing where it keeps no such record
    (commit 691dc66 and all before it)."""
    try:
        from batchai_retinanet_horovod_coco_tpu.obs import trace
    except ImportError:
        return None
    phases = getattr(trace, "phases", None)
    return None if phases is None else list(phases())


def self_times(phases: list) -> dict[int, float]:
    """A phase's duration less the part its children cover (their union,
    clipped to the phase)."""
    children: dict[int, list] = {}
    for p in phases:
        if p.parent is not None:
            children.setdefault(p.parent, []).append(p)
    out = {}
    for p in phases:
        covered, until = 0.0, p.t0
        for c in sorted(children.get(p.id, ()), key=lambda c: c.t0):
            start, stop = max(c.t0, until), min(c.t0 + c.dur, p.t0 + p.dur)
            if stop > start:
                covered, until = covered + stop - start, stop
        out[p.id] = max(0.0, p.dur - covered)
    return out


def beneath(phases: list, root_id: int) -> list:
    """Every phase under ``root_id``, at any depth."""
    children: dict[int, list] = {}
    for p in phases:
        children.setdefault(p.parent, []).append(p)
    out, todo = [], [root_id]
    while todo:
        below = children.get(todo.pop(), [])
        out += below
        todo += [p.id for p in below]
    return out


def _arg(p, key, default=None):
    return (p.args or {}).get(key, default)


def reduce(phases: list, t_open: float) -> dict:
    """The eight values of the phases that began before ``t_open``, each a
    number, or each ``None`` where the record is broken (rule (b)); with the
    table they were summed from under ``"table"``."""
    early = [p for p in phases if p.t0 < t_open]
    self_s = self_times(early)
    builds = sorted((p for p in early if p.name == STEP_BUILD), key=lambda p: p.t0)
    programs = [p for p in early if p.name == "xla_compile_or_load"]
    hits = [p for p in programs if _arg(p, "cache") == "hit"]
    compiled = [p for p in programs if _arg(p, "cache") != "hit"]

    def total(name: str) -> float:
        return float(sum(p.dur for p in early if p.name == name))

    values = {
        "setup.trace_lower_s": float(sum(self_s[p.id] for p in early if p.name in ("jit_trace", "jit_lower"))),
        "setup.cache_load_s": float(sum(_arg(p, "retrieval_s", 0.0) for p in hits)),
        "setup.backend_compile_s": float(sum(p.dur for p in compiled)),
        "setup.cache_misses": float(len(compiled)),
        "setup.step_builds": float(len(builds)),
        "setup.step_build_s": total(STEP_BUILD),
        "setup.init_state_s": total("init_state"),
        "setup.place_state_s": total("place_state"),
    }
    build_rows = []
    for b in builds:
        under = beneath(early, b.id)
        row = {"bucket": _arg(b, "bucket"), "call": _arg(b, "call"), "dur_s": b.dur, "rest_s": self_s[b.id]}
        for name in JAX_PHASES:
            row[name + "_s"] = sum(self_s[p.id] for p in under if p.name == name)
        row["cache"] = sorted({_arg(p, "cache") for p in under if p.name == "xla_compile_or_load"})
        row["jax_phases"] = sum(p.name in JAX_PHASES for p in under)
        build_rows.append(row)
    why = None
    if not builds:
        why = f"no {STEP_BUILD} phase began before the window"
    elif not any(row["jax_phases"] for row in build_rows):
        why = f"no {STEP_BUILD} phase holds a trace, a lowering or a compile of JAX's beneath it"
    names = sorted({p.name for p in early})
    table = {
        "by_name": {n: {"n": sum(p.name == n for p in early), "dur_s": total(n),
                        "self_s": sum(self_s[p.id] for p in early if p.name == n)} for n in names},
        "step_builds": build_rows,
        # the programs that cost most to bring up, whatever they were built under
        "longest": [[p.name, _arg(p, "fun"), round(p.dur, 4), _arg(p, "cache")] for p in
                    sorted((p for p in early if p.name in JAX_PHASES), key=lambda p: -p.dur)[:12]],
        "phases_before_window": len(early), "phases_after": len(phases) - len(early),
    }
    if why is not None:
        values = dict.fromkeys(METRICS)
        table["broken"] = why
    return dict(values, table=table)


def before_window(ctx) -> dict | None:
    """``reduce`` of the program's phases at this run's window, or nothing
    where the program keeps no record."""
    phases = program_phases()
    return None if phases is None else reduce(phases, ctx.run.t_open)


def values(ctx) -> dict:
    """``{metric: value}`` for this run by the rule in the module's
    docstring, worked out once and printed as ``benchmark: setup_phases
    {...}``."""
    if hasattr(ctx, "_setup_phases"):
        return ctx._setup_phases
    out = before_window(ctx)
    if out is None:
        say(NO_RECORD)
        out = dict.fromkeys(METRICS, 0.0)
    else:
        table = out.pop("table")
        if "broken" in table:
            say(f"the program's phase record is broken: {table['broken']}")
        print("benchmark: setup_phases", json.dumps({"values": out, **table}), flush=True)
    ctx._setup_phases = out
    return out


def value(ctx, metric: str) -> float | None:
    return values(ctx)[metric]


def main(argv: list[str] | None = None) -> int:
    """One traced run of a cell as ``run.py --trace 1`` makes it (the same
    set-up, warm call and measured call; no check of the outputs), then
    ``benchmark: setup_phases {...}``: the eight values, the phase table, and
    the benchmark's own four marks of the same set-up."""
    import argparse
    import shutil
    import time

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
    marks = [("process_start", bench_run.T_PROCESS), ("imports_devices_cache", time.perf_counter())]
    driver.setup()
    marks.append(("kind_setup", time.perf_counter()))
    driver.warm()
    marks.append(("kind_warm", time.perf_counter()))
    driver.measure()
    marks.append(("measured_call_until_window_opens", run.t_open))
    say("marks " + json.dumps({"setup_s": run.t_open - bench_run.T_PROCESS,
                               "setup_parts_s": {n: t - t0 for (n, t), (_, t0) in zip(marks[1:], marks)},
                               "setup_detail": getattr(driver, "setup_detail", None),
                               "step_s": getattr(driver, "step_s", None)}))
    ctx = layer_context.build(run, driver.facts, device_lib.device_info(run.devices))
    values(ctx)
    shutil.rmtree(run.tracer.dir, ignore_errors=True)  # hundreds of MB
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
