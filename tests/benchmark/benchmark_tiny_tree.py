"""A throw-away checkout for the benchmark's CPU tests.

Copies ``benchmark/`` into a temp directory and ADDS files beside it - a
configuration (``resnet_test``, 64x96), traffic mixes, cells, a per-layer
metric - plus the ``BENCHMARK.json`` entries that name them.  No file of the
copy is edited: that a cell, a configuration, a mix and a metric arrive as
new files plus new entries is what the tests show.  The device check is
replaced HERE, in a launcher the test writes (which also gives the CPU a
"published peak", so that the utilization reader has something to divide
by); ``run.py`` has no option for either.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KEYS = {"correct", "attempted", "failed", "metrics", "device"}

LAUNCHER = '''
import os, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, {repo!r})
import jax
from benchmark.harness import device, peaks
device.require_accelerator = lambda chips: jax.devices()[:chips]  # the test's stub
peaks.PEAKS["cpu"] = dict(peaks.PEAKS["TPU v5 lite"], source="the test's stub")
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
'''

TINY_METRIC = '''
"""A throw-away per-layer metric: batches the loop was handed."""


def read(ctx):
    return ctx.facts.get("steps", ctx.facts.get("batches"))
'''


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def build(root: str) -> str:
    """Make the throw-away checkout under ``root``; returns ``root``."""
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _snapshot(os.path.join(root, "benchmark"))
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))
    b = os.path.join(root, "benchmark")

    cfg = _load(os.path.join(b, "configs", "retinanet-r50-fpn-800.json"))
    cfg["name"] = "tiny"
    cfg["model"].update(backbone="resnet_test", stage_sizes=[1, 1, 1, 1], dtype="float32", num_classes=8)
    cfg["flops_model"].update(stage_sizes=[1, 1, 1, 1], num_classes=8)
    cfg["resize"] = {"min_side": 64, "max_side": 96}
    _dump(cfg, os.path.join(b, "configs", "tiny.json"))

    # A pair of configuration and mix may be named once, so the four-device
    # cell has a mix of its own, cut down from the one r50-train-dp4 names.
    for mix, tiny in (("train-loop-b8", "tiny-train"), ("train-loop-b8-dp4", "tiny-train-dp4")):
        t = _load(os.path.join(b, "traffic", mix + ".json"))
        t.update(per_chip_batch=2, bucket_hw=[64, 96], max_gt=8, boxes_per_image=[1, 4],
                 box_side_px=[8, 32], log_every=5, warm_steps=4, trace_steps=3,
                 # The tiny network's loss swings in its first steps, and how many
                 # steps fit the window depends on the machine: not this test's point.
                 loss_rise_tol=1.0)
        _dump(t, os.path.join(b, "traffic", tiny + ".json"))
    # The same mix with another rate declared than the optimizer is given:
    # the reference update then differs from the program's by half.
    _dump(dict(t, lr_per_image=2 * t["lr_per_image"]), os.path.join(b, "traffic", "tiny-train-wrong-rate.json"))
    with open(os.path.join(b, "layer_metrics", "tiny.work_units.py"), "w") as f:
        f.write(TINY_METRIC)

    bench["configs"].append({"name": "tiny", "source": "tests/benchmark", "file": "benchmark/configs/tiny.json",
                             "reduced": [], "why": "throw-away"})
    cells = {"tiny-train": ("tiny-train", 1, "r50-train-b8"), "tiny-dp4": ("tiny-train-dp4", 4, "r50-train-dp4"),
             "tiny-wrong-rate": ("tiny-train-wrong-rate", 1, "r50-train-b8")}
    for name, (traffic, chips, like) in cells.items():
        bench["workloads"].append({"name": name, "config": "tiny", "traffic": traffic, "chips": chips,
                                   "why": "throw-away"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    bench["per_layer"].append({"name": "tiny.work_units", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "train loop", "moves": "setup_s"})
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs), "a pair of configuration and mix may be named once"
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    with open(os.path.join(root, "launch.py"), "w") as f:
        f.write(LAUNCHER.format(repo=REPO))
    after = _snapshot(os.path.join(root, "benchmark"))
    assert all(after[p] == digest for p, digest in before.items()), "an existing file was edited"
    return root


def _snapshot(directory: str) -> dict:
    import hashlib

    out = {}
    for base, _dirs, files in os.walk(directory):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, directory)] = hashlib.sha256(f.read()).hexdigest()
    return out


def run_cell(root: str, workload: str, trace: int, devices: int = 1, seconds: float = 2.0):
    """Run one cell of the throw-away checkout in a new process on the CPU;
    returns (returncode, last line parsed or None, whole output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(REPO, "tests", ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "launch.py"), "--workload", workload, "--seed", "0",
         "--seconds", str(seconds), "--trace", str(trace)],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    try:
        last = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        last = None
    return proc.returncode, last, proc.stdout + proc.stderr


def said(out: str, prefix: str) -> list[str]:
    """What the run printed after ``prefix`` at the start of a line."""
    return [l[len(prefix):].strip() for l in out.splitlines() if l.startswith(prefix)]


def facts(out: str) -> dict:
    return json.loads(said(out, "benchmark: facts")[-1])
