"""The benchmark's one command: run one cell once, in this process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Demands a TPU (no fallback), builds weights on the device from the seed,
loads or compiles only the cell's programs, warms them up, measures for
``--seconds``, checks the outputs, and prints one JSON line last.  With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a few steps of device trace
and the program's counters.

Everything that belongs to one configuration, traffic mix, traffic kind or
per-layer metric is a file found by the name in ``BENCHMARK.json``; see
``benchmark/README.md``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_module(kind_dir: str, name: str):
    path = os.path.join(HERE, kind_dir, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {kind_dir} file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind_dir}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def metrics_for(entries: list[dict], cell: str) -> list[dict]:
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def prepare(workload: str, seed: int, seconds: float, trace: bool):
    """BENCHMARK.json's cell -> (bench, run, driver), on the accelerator, with
    the compile cache placed and the compile counter listening."""
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in BENCHMARK.json")
    config = load_json(next(c for c in bench["configs"] if c["name"] == cell["config"])["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")

    from benchmark.harness import device as device_lib
    from benchmark.harness.compile_counter import CompileCounter
    from benchmark.harness.runctx import Run, Tracer

    import jax

    # Everything is stored, the sub-second programs too: a run after the
    # first compiles nothing.  The directory is the program's own choice
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from batchai_retinanet_horovod_coco_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    devices = device_lib.require_accelerator(cell["chips"])
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell=cell, config=config, traffic=traffic, seed=seed, seconds=seconds,
              devices=devices, tracer=Tracer(trace, out_dir), out_dir=out_dir,
              t_process=T_PROCESS, counter=CompileCounter())
    return bench, run, load_module("kinds", traffic["kind"]).Driver(run)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import shutil

    from benchmark.harness import device as device_lib

    bench, run, driver = prepare(args.workload, args.seed, args.seconds, bool(args.trace))
    cell, devices, tracer, counter = run.cell, run.devices, run.tracer, run.counter
    if args.trace:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    print(f"benchmark: {cell['name']} on {len(devices)} x {devices[0].device_kind}, "
          f"seed {args.seed}, {args.seconds} s, trace {args.trace}", flush=True)

    marks = [("imports_devices_cache", time.perf_counter())]
    driver.setup()
    marks.append(("kind_setup", time.perf_counter()))
    driver.warm()
    marks.append(("kind_warm", time.perf_counter()))
    result = driver.measure()
    marks.append(("measured_call_until_window_opens", run.t_open))
    compiles = counter.snapshot()
    device = device_lib.device_info(devices)
    memory_stats = device_lib.memory_stats(devices[0])
    compiles_in_window = compiles["requests"] - run.compiles_at_open
    setup_s = run.t_open - T_PROCESS

    problems = driver.check()
    if compiles_in_window:
        problems.append(f"{compiles_in_window} programs were compiled inside the window")
    if result["failed"]:
        problems.append(f"{result['failed']} of {result['attempted']} failed")
    if hasattr(driver, "close"):
        driver.close()
    facts = dict(driver.facts, compiles_in_window=compiles_in_window,
                 compile_requests=compiles["requests"], cache_hits=compiles["hits"],
                 compile_s=compiles["compile_s"], setup_s=setup_s,
                 # where set-up went, in order; the parts sum to setup_s
                 setup_parts_s={name: t - t0 for (name, t), t0 in
                                zip(marks, [T_PROCESS] + [t for _n, t in marks])},
                 memory_stats=memory_stats)
    print("benchmark: facts", json.dumps(facts, default=str), flush=True)

    line = {"attempted": result["attempted"], "failed": result["failed"]}
    values = dict(result["end_to_end"], setup_s=setup_s)
    if args.trace:
        from benchmark.harness import layer_context

        ctx = layer_context.build(run, facts, device)
        wanted = metrics_for(bench["per_layer"], cell["name"])
        values = {m["name"]: load_module("layer_metrics", m["name"]).read(ctx) for m in wanted}
        # A reader that finds nothing returns nothing and its metric is left
        # out of the line; for a metric BENCHMARK.json lists for this cell
        # that is a fault of the run, said aloud, never a silent omission.
        for name, value in values.items():
            if value is None:
                problems.append(f"per-layer metric {name} is listed for this cell and its reader found nothing to read")
        busy = ctx.device_trace_facts()
        if busy.get("busy_s", 0) > 0:
            device.update(busy)
        else:
            problems.append("the trace holds no device operation inside the window")
        line["breakdown"] = ctx.breakdown()
        shutil.rmtree(tracer.dir, ignore_errors=True)  # hundreds of MB; the summary stays
    else:
        wanted = metrics_for(bench["end_to_end"], cell["name"])
    for p in problems[:20]:
        print("benchmark: NOT CORRECT:", p, flush=True)
    line["correct"] = not problems
    line["metrics"] = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                       for m in wanted if values.get(m["name"]) is not None}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
