"""``harness/program_trace.py``: the program's spans off the profiler's host
planes, and the step's device time by named scope, on the trace recorded on
a v5e (``fixture.xplane.pb``) with hand-made scope tables; the portrait mix
against the mix it is cut from and the cell that names it; and the by-hand
run (``main``) on the CPU, where the program's spans are read end to end."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as run_lib  # noqa: E402
from benchmark.harness import program_trace as pt  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402
from benchmark.harness.compile_counter import CompileCounter  # noqa: E402
from benchmark.harness.layer_context import LayerContext  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
E = tr.Event

SLICES = ("backbone", "fpn", "heads", "assign", "loss", "optimizer", "unscoped")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(os.path.join(HERE, "fixture.xplane.pb"))


def _ctx(trace, tmp_path, traffic=None):
    marks = {e.name: e for e in trace.host}
    window = (marks["bench.window_open"].start, marks["bench.window_close"].end)
    run = types.SimpleNamespace(out_dir=str(tmp_path), tracer=types.SimpleNamespace(dir=str(tmp_path / "none")),
                                counter=None, traffic=traffic or {}, config={})
    return LayerContext(run=run, facts={"module_pattern": "train_step"}, device={}, peaks={"flops_bf16": 197e12},
                        trace=trace, window=window, host_spans=[])


def _table(trace, **scope_by_prefix):
    """Every instruction of the recording, filed by the start of its name."""
    table = {}
    for e in trace.devices[0].ops:
        name = tr.op_name(e.name)
        scope = next((s for p, s in scope_by_prefix.items() if name.startswith(p.replace("_", "-"))
                      or name.startswith(p)), "unscoped")
        table[name] = (scope, "fwd", f"{scope}/{name}")
    return table


@pytest.mark.parametrize("ops,expected", [
    # one after another: each its own length
    ([E("a", 0, 10), E("b", 10, 20), E("c", 25, 30)], {"a": 10, "b": 10, "c": 5}),
    # operations inside another (a loop and its body): the outer keeps what is left
    ([E("w", 0, 100), E("a", 10, 20), E("b", 20, 50), E("c", 60, 70), E("d", 100, 110)],
     {"w": 50, "a": 10, "b": 30, "c": 10, "d": 10}),
    ([E("w", 0, 100), E("a", 10, 50), E("a1", 20, 30), E("c", 60, 70)], {"w": 50, "a": 30, "a1": 10, "c": 10}),
    # overlapping without nesting: no instant counted twice
    ([E("a", 0, 10), E("b", 5, 15), E("c", 12, 14)], {"a": 5, "b": 8, "c": 2}),
])
def test_self_times_sum_to_the_union(ops, expected):
    got = {e.name: ns for e, ns in pt.self_times(ops)}
    assert got == expected
    assert sum(got.values()) == tr.total(tr.union([(e.start, e.end) for e in ops]))


def test_slices_on_the_recording_sum_to_the_busy_time(recorded, tmp_path, capsys):
    ctx = _ctx(recorded, tmp_path)
    table = _table(recorded, convolution="heads", copy="optimizer")
    got = pt.slices(ctx, table, {"heads": ("cls",)})
    assert got is pt.slices(ctx) and got["runs"] == 5  # once per run
    assert got["coverage"] == 1.0 and set(got["ms"]) == {"heads", "optimizer", "unscoped"}
    busy = tr.per_module_busy_ms(recorded, "train_step", ctx.window)
    assert got["total_ms"] == pytest.approx(tr.median(busy), rel=1e-9)
    assert sum(got["ms"].values()) == pytest.approx(got["total_ms"], rel=0.01)
    assert all(v > 0 for v in got["ms"].values())
    # three matmul fusions a run, longest first, each with its scope path
    top = got["longest_ops"]["heads"]
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1] and top[0][3].startswith("heads/convolution")
    assert got["by_scope"]["heads"]["-"]["fwd"] == pytest.approx(sum(op[1] for op in top), rel=1e-2)  # rounded
    with open(tmp_path / "slices.json") as f:
        assert json.load(f)["ms"] == got["ms"]
    printed = [l for l in capsys.readouterr().out.splitlines() if l.startswith("benchmark: slices ")]
    assert len(printed) == 1 and json.loads(printed[0][len("benchmark: slices "):])["coverage"] == 1.0
    assert pt.slice_ms(ctx, "heads") == got["ms"]["heads"] and pt.slice_ms(ctx, "fpn") == 0.0


def test_a_table_that_misses_an_operation_reads_nothing(recorded, tmp_path, capsys):
    """Another compilation's instruction names are not the trace's: under 99%
    of the device time found means no slices, said aloud."""
    ctx = _ctx(recorded, tmp_path)
    table = _table(recorded, convolution="heads")
    longest = max(recorded.devices[0].ops, key=lambda e: e.end - e.start)
    del table[tr.op_name(longest.name)]
    assert pt.slices(ctx, table) is None
    out = capsys.readouterr().out
    assert "no slices" in out and tr.op_name(longest.name) in out
    with open(tmp_path / "slices.json") as f:
        seen = json.load(f)
    assert seen["coverage"] < pt.MIN_COVERAGE and seen["not_in_table"][0][0] == tr.op_name(longest.name)
    # what was not found is counted as unscoped: the slices still sum to the busy time
    assert sum(seen["ms"].values()) == pytest.approx(seen["total_ms"], rel=0.01)
    assert pt.slice_ms(ctx, "heads") is None and pt.slice_mfu_pct(ctx, "heads") is None
    assert set(pt.report(ctx)) == {"spans_in_window"}


class _FakeCompiled:
    def __init__(self, names, scopes):
        self.text = "\n".join(
            f'  %{n} = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="jit(train_step)/{scopes(n)}/mul"}}'
            for n in names)

    def as_text(self):
        return self.text


def _program_with(monkeypatch, compiled):
    from batchai_retinanet_horovod_coco_tpu.train import loop

    monkeypatch.setattr(loop, "compiled_step", lambda: compiled)


def test_slices_from_the_programs_compiled_step(recorded, tmp_path, monkeypatch):
    """The table comes from ``train/loop.py::compiled_step`` through
    ``train/step.py::scope_table``; ``report`` divides it up."""
    names = sorted({tr.op_name(e.name) for e in recorded.devices[0].ops})
    slices = ("backbone", "heads", "assign", "loss", "optimizer")
    _program_with(monkeypatch, _FakeCompiled(names, lambda n: slices[names.index(n) % len(slices)]))
    traffic = {"bucket_hw": [1344, 800], "per_chip_batch": 8}
    ctx = _ctx(recorded, tmp_path, traffic)
    with open(os.path.join(REPO, "benchmark", "configs", "retinanet-r50-fpn-800.json")) as f:
        ctx.run.config = json.load(f)
    ctx.run.counter = CompileCounter()
    values = pt.report(ctx)
    assert values["coverage"] == 1.0 and pt.slice_ms(ctx, "fpn") == 0.0 and values["train_step.heads_ms"] > 0
    device_ms = run_lib.load_module("layer_metrics", "train_step.device_ms").read(ctx)
    assert values["train_step.device_ms"] == pytest.approx(device_ms, rel=1e-9)
    assert sum(pt.slice_ms(ctx, s) for s in SLICES) == pytest.approx(device_ms, rel=0.01)
    assert values["train_step.backbone_mfu_pct"] == pt.slice_mfu_pct(ctx, "backbone") > 0
    # 3 x 2 x MACs x batch over the slice's time over the peak (a toy time here)
    from benchmark.harness import flops

    macs = flops.forward_macs(ctx.run.config["flops_model"], (1344, 800))["heads"]
    assert values["train_step.heads_mfu_pct"] == pytest.approx(
        100 * 6 * macs * 8 / (values["train_step.heads_ms"] / 1e3) / 197e12)


def test_an_executable_from_before_the_scopes_reads_nothing(recorded, tmp_path, monkeypatch, capsys):
    """jax leaves metadata out of the compile-cache key: a cache filled before
    the program had its scopes hands back an executable that names only the
    model's.  Every instruction is found, and still there are no slices."""
    names = sorted({tr.op_name(e.name) for e in recorded.devices[0].ops})
    _program_with(monkeypatch, _FakeCompiled(names, lambda n: "jvp(RetinaNet)/backbone"))
    assert pt.slices(_ctx(recorded, tmp_path)) is None
    out = capsys.readouterr().out
    assert "before the program had its scopes" in out and "'loss'" in out


def test_a_program_without_the_scopes_reads_nothing_and_raises_nothing(recorded, tmp_path, monkeypatch):
    """What the parent of the PR that brought this file gives it."""
    from batchai_retinanet_horovod_coco_tpu.train import loop, step

    monkeypatch.delattr(loop, "compiled_step")
    monkeypatch.delattr(step, "scope_table")
    ctx = _ctx(recorded, tmp_path)
    assert pt.slices(ctx) is None and pt.slice_mfu_pct(ctx, "backbone") is None
    assert pt.report(ctx) == {"spans_in_window": {}}


def test_no_step_built_reads_nothing(recorded, tmp_path, capsys):
    from batchai_retinanet_horovod_coco_tpu.train import loop

    loop._built_steps.clear()
    assert pt.slices(_ctx(recorded, tmp_path)) is None
    assert "no compiled step" in capsys.readouterr().out


def test_a_trace_without_a_device_reads_nothing(tmp_path):
    ctx = _ctx(tr.Trace([], [E("bench.window_open", 0, 1), E("bench.window_close", 9, 10)]), tmp_path)
    assert pt.slices(ctx) is None and pt.span_ms(ctx, "step") == []
    ctx.trace = None
    assert pt.slices(ctx) is None and pt.host_spans(ctx) == [] and pt.traced_window(ctx) is None


def test_host_spans_are_read_inside_the_window_marks(recorded, tmp_path, monkeypatch):
    """The program's spans are filtered by the benchmark's window marks, not
    by the steady stretch of device runs: a step is dispatched before it runs."""
    ctx = _ctx(recorded, tmp_path)
    lo, hi = pt.traced_window(ctx)
    runs = tr.module_events(recorded.devices[0], "train_step", (lo, hi))
    ctx.window = tr.quietest_stretch(runs, 2)
    assert pt.traced_window(ctx) == (lo, hi) != ctx.window
    spans = [E("rn.step", lo - 50, lo - 10), E("rn.step", lo + 10, lo + 2_000_010), E("rn.step", ctx.window[0] + 5, ctx.window[0] + 4_000_005),
             E("rn.device-prefetch", lo + 20, lo + 500_020), E("rn.step", hi + 1, hi + 9)]
    monkeypatch.setattr(pt, "host_spans", lambda ctx, prefix=pt.PREFIX: spans)
    assert pt.span_ms(ctx, "step") == [2.0, 4.0]
    assert pt.report(ctx) == {"spans_in_window": {"rn.step": 2, "rn.device-prefetch": 1},
                              "train_loop.dispatch_ms": 3.0, "train_loop.h2d_ms": 0.5}
    # the recording itself holds none of the program's spans
    monkeypatch.undo()
    assert pt.span_ms(ctx, "step") == []


# ---- the portrait cell's data ------------------------------------------------


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_the_portrait_mix_is_train_loop_b8_at_the_other_bucket():
    b8, portrait = _mix("train-loop-b8"), _mix("train-loop-b8-portrait")
    assert b8.pop("what") != portrait.pop("what")
    assert b8.pop("bucket_hw") == [800, 1344] and portrait.pop("bucket_hw") == [1344, 800]
    assert b8 == portrait


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_portrait_cell_is_one_chip_of_the_flagship(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == "r50-train-b8-portrait")
    assert cell == dict(cell, config="retinanet-r50-fpn-800", traffic="train-loop-b8-portrait", chips=1)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "train_img_per_s_chip")
    assert rate["workloads"][-1] == cell["name"] and rate["bound"] == 0.01


def test_the_portrait_cell_lists_what_the_flagship_cell_lists_and_nothing_new(bench):
    """A listed metric whose reader finds nothing makes a run NOT CORRECT
    (``run.py``), and the parent of PR 23 has neither spans nor scopes: the
    cell reports the metrics the benchmark had, and the PR lists none."""
    listed = {c: [m["name"] for m in bench["per_layer"] if "workloads" not in m or c in m["workloads"]]
              for c in ("r50-train-b8", "r50-train-b8-portrait")}
    assert listed["r50-train-b8-portrait"] == listed["r50-train-b8"] == [
        "setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.device_ms", "train_step.mfu_pct",
        "assign_fused.kernel_ms", "assign_fused_roofline"]
    assert all(m["workloads"][-1] == "r50-train-b8-portrait" for m in bench["per_layer"][1:])


# ---- by hand, end to end on the CPU ------------------------------------------

LAUNCHER = '''
import os, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, {repo!r})
import jax
from benchmark.harness import device, peaks
device.require_accelerator = lambda chips: jax.devices()[:chips]  # the test's stub
peaks.PEAKS["cpu"] = dict(peaks.PEAKS["TPU v5 lite"], source="the test's stub")
from benchmark.harness import program_trace
sys.exit(program_trace.main(sys.argv[1:]))
'''


@pytest.mark.parametrize("workload,devices", [("tiny-train", 1), ("tiny-dp4", 4)])
def test_main_reads_the_programs_spans_off_a_cpu_trace(tmp_path, workload, devices):
    """The loop's spans reach the profiler with the ring never enabled and
    are read back inside the window; a CPU trace has no device plane, so no
    slices.  The throw-away checkout is the frozen tests' own."""
    sys.path.insert(0, HERE)
    import benchmark_tiny_tree as tiny

    root = tiny.build(str(tmp_path / "tree"))
    with open(os.path.join(root, "launch_program_trace.py"), "w") as f:
        f.write(LAUNCHER.format(repo=REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(REPO, "tests", ".jax_cache"))
    proc = subprocess.run([sys.executable, os.path.join(root, "launch_program_trace.py"), "--workload", workload,
                           "--seconds", "2"], env=env, cwd=root, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    got = json.loads(tiny.said(proc.stdout, "benchmark: program_trace")[-1])
    steps = got["spans_in_window"]["rn.step"]
    assert steps >= 3 and abs(got["spans_in_window"]["rn.data_wait"] - steps) <= 1  # trace_steps of the tiny mix
    assert got["spans_in_window"]["rn.device-prefetch"] >= 1
    assert got["train_loop.dispatch_ms"] > 0 and got["train_loop.h2d_ms"] > 0
    assert not [k for k in got if k.startswith("train_step.")]
    assert not os.path.exists(os.path.join(root, ".bench_out", workload, "xplane"))
