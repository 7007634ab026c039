"""The yardstick's own arithmetic: trace reduction, FLOP model, peaks, the
update rule of the train reference, and BENCHMARK.json against the files it
names."""

import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import flops, peaks  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


# ---- trace reduction on hand-made intervals --------------------------------


def test_union_subtract_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tr.total(tr.clip([(0, 10)], 3, 5)) == 2


def _plane(name, ops, modules):
    return tr.DevicePlane(name, [tr.Event(*o) for o in ops], [tr.Event(*m) for m in modules])


def test_busy_idle_and_per_step_split():
    # Two steps of 100 ns; the first is busy 80, the second 60; a 50 ns gap between.
    d0 = _plane("/device:TPU:0",
                [("fusion.1", 0, 50), ("fusion.2", 60, 90), ("fusion.1", 150, 210)],
                [("jit_train_step(1)", 0, 100), ("jit_train_step(1)", 150, 250)])
    d1 = _plane("/device:TPU:1", [("fusion.1", 0, 100), ("fusion.1", 150, 250)],
                [("jit_train_step(1)", 0, 100), ("jit_train_step(1)", 150, 250)])
    trace = tr.Trace([d0, d1], [])
    b = tr.busy_and_idle(trace, (0, 250))
    assert b["per_device_busy_s"] == [140e-9, 200e-9]
    assert b["busy_s"] == pytest.approx(170e-9) and b["window_s"] == pytest.approx(250e-9)
    assert b["idle_share_worst"] == pytest.approx(1 - 140 / 250)  # the idlest device
    assert sorted(tr.per_module_busy_ms(trace, "train_step")) == pytest.approx(
        sorted([80e-6, 60e-6, 100e-6, 100e-6]))
    # A window that cuts the second step leaves it out.
    assert len(tr.per_module_busy_ms(trace, "train_step", (0, 200))) == 2
    assert tr.op_time_per_module_ms(trace, r"^fusion\.2", "train_step") == pytest.approx(
        [30e-6, 0.0, 0.0, 0.0])


def test_exposed_collective_arithmetic():
    ops = [tr.Event("fusion.1", 0, 10), tr.Event("all-reduce-start.1", 10, 11),
           tr.Event("fusion.2", 11, 20), tr.Event("all-reduce-done.1", 20, 26),
           tr.Event("all-reduce.5", 30, 34), tr.Event("fusion.3", 32, 40)]
    total, exposed = tr.collective_split(ops)
    # The pair covers [10, 26], the synchronous one [30, 34]: 20 in all.
    assert total == 20
    # Compute covers [11, 20] of the pair and [32, 34] of the other.
    assert exposed == 1 + 6 + 2
    # The name as the v5e's trace printed it (r50-train-dp4, my chip run, PR 22):
    # one synchronous tuple all-reduce on the operations' line, all of it exposed.
    chip = tr.Event("%all-reduce.5 = (f32[1,1,64,64]{3,2,1,0:T(8,128)S(1)}, f32[3,3,64,64]{3,2,1,0:T(8,128)}) "
                    "all-reduce(f32[1,1,64,64]{3,2,1,0:T(8,128)S(1)} %fusion.1, f32[3,3,64,64] %fusion.2)", 50, 60)
    assert tr.op_name(chip.name) == "all-reduce.5"
    assert tr.collective_split([tr.Event("fusion.9", 40, 50), chip, tr.Event("fusion.10", 60, 70)]) == (10, 10)
    d = _plane("/device:TPU:0", [("fusion.9", 40, 50), (chip.name, 50, 60), ("fusion.10", 60, 70)],
               [("jit_sharded_step(7)", 40, 70)])
    out = tr.collectives_per_module_ms(tr.Trace([d, d], []), "train_step|sharded_step")
    assert out == {"total_ms": [10e-6, 10e-6], "exposed_ms": [10e-6, 10e-6]}


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    d = _plane("/device:TPU:0", [("a", 0, 100_000), ("b", 200_000, 300_000), ("c", 300_005, 400_000)], [])
    trace = tr.Trace([d], [])
    spans = [tr.Event("bench.fetch", 90_000, 210_000), tr.Event("bench.next_batch", 0, 400_000)]
    out = dict(tr.idle_gaps_by_host_activity(trace, (0, 400_000), spans))
    assert out["bench.fetch"] == pytest.approx(100_000e-9)  # the shorter span wins
    assert out["between_ops_short"] == pytest.approx(5e-9)
    assert tr.top_ops(trace, (0, 400_000))[0][0] in ("a", "b", "c")


# Program runs [start ms, length ms] on device 0 as the v5e's traces showed
# them (trace_summary.json of my chip runs, PR 22): the profiler's start-up
# stall after three runs, then stretches of back-to-back runs between stalls
# of about a second (a device-to-host copy under the profiler).
CHIP_TIMELINES = {
    "r50-train-b8": [[0.0, 118.32], [118.32, 118.32], [236.65, 118.33], [3955.4, 118.59], [4074.0, 118.32],
                     [4192.33, 118.33], [4310.66, 118.33], [4428.99, 118.32], [4547.32, 118.33], [4665.65, 118.33],
                     [4783.99, 118.33], [4902.32, 118.35], [5020.68, 118.33], [5139.01, 118.32], [5257.34, 118.33],
                     [5375.68, 118.33], [6342.91, 118.41], [6461.33, 118.33], [6579.67, 118.33], [6698.0, 118.33],
                     [6823.11, 118.33], [6941.45, 118.31], [7059.77, 118.33], [8230.18, 118.43]],
    "r50-train-dp4": [[0.0, 121.11], [121.12, 121.07], [242.19, 121.06], [9165.83, 131.09], [9296.92, 9043.18],
                      [18340.11, 121.07], [18461.18, 121.07], [18582.25, 121.07], [18703.33, 121.07],
                      [18824.41, 121.08], [18945.49, 121.07], [19066.57, 121.07], [19187.65, 121.07],
                      [21962.66, 139.75], [22102.42, 121.07], [22223.5, 121.07], [22344.58, 121.07],
                      [22465.65, 121.07]],
}


def _timeline_plane(timeline, busy_share=0.998):
    """A device plane with those program runs; inside each run one operation
    that covers ``busy_share`` of it (118.118 of 118.33 ms on the chip)."""
    ns = lambda ms: int(round(ms * 1e6))
    runs = [("jit_train_step(1)", ns(s), ns(s + d)) for s, d in timeline]
    return _plane("/device:TPU:0", [("fusion.1", s, s + int((e - s) * busy_share)) for _n, s, e in runs], runs)


@pytest.mark.parametrize("cell", sorted(CHIP_TIMELINES))
def test_steady_window_is_the_quietest_stretch_of_runs(cell):
    """What the v5e's traced runs looked like: the last ten runs (the rule
    before) straddle a stall on one chip and read 62% idle; the quietest
    five consecutive runs lie between stalls on one chip and on four."""
    d = _timeline_plane(CHIP_TIMELINES[cell])
    runs = tr.module_events(d, "train_step")
    lo, hi = tr.quietest_stretch(runs, 5)
    inside = [r for r in runs if lo <= r.start and r.end <= hi]
    assert len(inside) == 5
    gaps = [b.start - a.end for a, b in zip(inside, inside[1:])]
    assert max(gaps) < 0.1e6  # back to back: under 0.1 ms between runs
    b = tr.busy_and_idle(tr.Trace([d], []), (lo, hi))
    assert b["idle_share_worst"] == pytest.approx(0.002, abs=0.0005)
    assert b["window_s"] == pytest.approx(5 * CHIP_TIMELINES[cell][1][1] / 1e3, rel=0.002)
    last_ten = (runs[-10].start, runs[-1].end)
    assert tr.busy_and_idle(tr.Trace([d], []), last_ten)["idle_share_worst"] > 0.5


def test_quietest_stretch_shows_a_host_that_cannot_keep_up():
    """A gap before EVERY run leaves no stretch free of it; too few runs give no stretch."""
    runs = [tr.Event("jit_train_step(1)", i * 150, i * 150 + 100) for i in range(8)]
    lo, hi = tr.quietest_stretch(runs, 5)
    assert hi - lo == 5 * 100 + 4 * 50
    assert tr.quietest_stretch(runs[:4], 5) is None and tr.quietest_stretch([], 1) is None
    # One slow run among fast ones is left out.
    runs[3] = tr.Event("jit_train_step(1)", 450, 1450)
    runs[4:] = [tr.Event("jit_train_step(1)", 1500 + i * 150, 1600 + i * 150) for i in range(6)]
    assert tr.quietest_stretch(runs, 5)[0] == 1500


def test_context_reads_busy_and_window_from_the_trace_alone():
    from benchmark.harness.layer_context import LayerContext

    d = _timeline_plane(CHIP_TIMELINES["r50-train-b8"])
    runs = tr.module_events(d, "train_step")
    ctx = LayerContext(run=None, facts={"module_pattern": "train_step"}, device={}, peaks=None,
                       trace=tr.Trace([d], []), window=tr.quietest_stretch(runs, 5), host_spans=[])
    out = ctx.device_trace_facts()
    assert set(out) == {"busy_s", "window_s"}
    assert out["window_s"] == pytest.approx(0.59165, rel=1e-3) and 0.997 < out["busy_s"] / out["window_s"] < 0.999
    ctx.trace = None
    assert ctx.device_trace_facts() == {}


# ---- trace reduction on a recorded trace -----------------------------------


def test_recorded_tpu_trace():
    """``fixture.xplane.pb``: five runs of a jitted ``train_step`` on a v5e
    inside the benchmark's window marks (``record_fixture.py``)."""
    trace = tr.load(os.path.join(HERE, "fixture.xplane.pb"))
    assert len(trace.devices) == 1 and trace.devices[0].ops
    marks = {e.name: e for e in trace.host}
    assert {"bench.window_open", "bench.window_close", "bench.next_batch"} <= set(marks)
    window = (marks["bench.window_open"].start, marks["bench.window_close"].end)
    steps = tr.module_events(trace.devices[0], "train_step", window)
    assert len(steps) == 5
    per_step = tr.per_module_busy_ms(trace, "train_step", window)
    assert len(per_step) == 5 and all(0 < ms <= (s.end - s.start) / 1e6 + 1e-9
                                      for ms, s in zip(per_step, steps))
    b = tr.busy_and_idle(trace, window)
    assert 0 < b["busy_s"] < b["window_s"]
    assert b["busy_s"] == pytest.approx(sum(per_step) / 1e3, rel=0.05)
    # The recording sleeps on the host: the device is idle most of the window.
    assert 0.3 < b["idle_share_worst"] < 1.0
    gaps = tr.idle_gaps_by_host_activity(trace, window, [e for e in trace.host if e.name == "bench.next_batch"])
    assert gaps and all(s >= 0 for _, s in gaps)


def test_context_built_from_the_recorded_trace(tmp_path):
    """``layer_context.build`` and a reader on the v5e recording, as a
    traced run has them: the steady window is the quietest stretch of runs
    inside the window marks, ``busy_s`` and ``window_s`` are that stretch's."""
    import shutil
    import types

    from benchmark import run as run_lib
    from benchmark.harness import layer_context

    profile = tmp_path / "xplane" / "plugins" / "profile" / "recorded"
    profile.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "fixture.xplane.pb"), profile / "fixture.xplane.pb")
    run = types.SimpleNamespace(tracer=types.SimpleNamespace(dir=str(tmp_path / "xplane")),
                                out_dir=str(tmp_path))
    facts = {"module_pattern": "train_step", "trace_steady_runs": 2}
    ctx = layer_context.build(run, facts, {"kind": "TPU v5 lite", "platform": "tpu"})
    runs = tr.module_events(ctx.trace.devices[0], "train_step")
    assert len(runs) == 5 and ctx.window == tr.quietest_stretch(runs, 2)
    inside = tr.module_events(ctx.trace.devices[0], "train_step", ctx.window)
    assert len(inside) == 2
    busy = ctx.device_trace_facts()
    assert 0 < busy["busy_s"] <= busy["window_s"] == (ctx.window[1] - ctx.window[0]) / 1e9
    per_run = run_lib.load_module("layer_metrics", "train_step.device_ms").read(ctx)
    assert 0 < per_run <= max(r.end - r.start for r in inside) / 1e6
    assert ctx.peaks["flops_bf16"] == 197e12
    assert json.load(open(tmp_path / "trace_summary.json"))["devices"][0]["modules"]
    # No kernel of that name in the recording: the reader finds nothing.
    facts.update(assign_pattern="assign_fused", assign_cost=flops.assign_fused_cost(8, 201600, 100))
    assert run_lib.load_module("layer_metrics", "assign_fused.kernel_ms").read(ctx) is None
    assert run_lib.load_module("layer_metrics", "assign_fused_roofline").read(ctx) is None
    with pytest.raises(KeyError, match="no published peaks"):
        layer_context.build(run, facts, {"kind": "TPU v9 imaginary", "platform": "tpu"})


# ---- FLOP model against the program's forward ------------------------------


def _jaxpr_conv_macs(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            spatial = int(np.prod([rhs[i] for i in dn.rhs_spec[2:]]))
            cin_per_group = rhs[dn.rhs_spec[1]]
            total += int(np.prod(out)) * spatial * cin_per_group
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    total += _jaxpr_conv_macs(inner)
    return total


# The program's space-to-depth stem does 4x4x12 = 192 multiply-adds per
# output where the published 7x7x3 stem does 147: +0.8% of R50's total.
@pytest.mark.parametrize("config_file,explained", [("retinanet-r50-fpn-800.json", 0.010)])
def test_flop_model_against_the_programs_jaxpr(config_file, explained):
    import jax
    import jax.numpy as jnp

    from benchmark.harness import model as model_lib

    with open(os.path.join(REPO, "benchmark", "configs", config_file)) as f:
        config = json.load(f)
    hw = (800, 1344)
    model = model_lib.build_model(config)
    images = jax.ShapeDtypeStruct((1, *hw, 3), jnp.float32)
    variables = jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 64, 64, 3)))
    jaxpr = jax.make_jaxpr(lambda v, x: model.apply(v, x, train=False))(variables, images)
    counted = _jaxpr_conv_macs(jaxpr.jaxpr)
    modelled = flops.forward_macs(config["flops_model"], hw)
    assert modelled["anchors"] == 201600
    rel = (counted - modelled["total"]) / modelled["total"]
    assert abs(rel) < 0.02, (counted, modelled)
    assert abs(rel) <= explained, rel


def test_roofline_names_its_bound():
    cost = flops.assign_fused_cost(8, 201600, 100)
    share = flops.roofline_share(cost, 1.15e-3, peaks.peaks_for("TPU v5 lite"))
    assert share["bound"] == "bytes" and 0.05 < share["share"] < 0.12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


# ---- the train reference's update rule ---------------------------------------


def _load_kind(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location("kind_" + name, os.path.join(REPO, "benchmark", "kinds", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("grad_scale,clipped", [(1.0, False), (100.0, True)])
def test_update_report_against_the_programs_optimizer(grad_scale, clipped):
    """The plainly written first update (clip, decayed weights, -lr x) equals
    what the program's optimizer chain does, clipped or not; a skipped
    update, a doubled rate and a dropped gradient leaf all show."""
    import jax
    import jax.numpy as jnp

    from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer

    kind = _load_kind("train_loop")
    rng = np.random.default_rng(0)
    params = {"a": rng.normal(0, 0.05, (64, 32)).astype(np.float32), "b": {"c": rng.normal(0, 0.05, 100).astype(np.float32)}}
    grads = jax.tree.map(lambda p: (grad_scale * rng.normal(0, 0.1, p.shape)).astype(np.float32), params)
    tx, _ = make_optimizer(OptimizerConfig(schedule="constant", warmup_steps=0, base_lr=0.32, global_batch_size=8,
                                           momentum=0.9, weight_decay=1e-4, clip_global_norm=10.0))
    updates, _ = tx.update(jax.tree.map(jnp.asarray, grads), tx.init(params), params)
    after = jax.device_get(jax.tree.map(lambda p, u: p + u, params, updates))
    g64 = [np.asarray(g, np.float64) for g in jax.tree.leaves(grads)]
    norm = float(np.sqrt(sum(np.sum(g * g) for g in g64)))
    assert (norm > 10.0) == clipped
    recipe = dict(lr=0.00125 * 8, weight_decay=1e-4, clip=10.0)
    rep = kind._update_report(params, after, g64, norm, **recipe)
    assert rep["rel_diff"] < 1e-3, rep  # float32 storage of the parameters
    assert kind._update_report(params, params, g64, norm, **recipe)["rel_diff"] == pytest.approx(1.0)
    assert kind._update_report(params, after, g64, norm, **dict(recipe, lr=0.02))["rel_diff"] == pytest.approx(0.5, abs=1e-3)
    dropped = [g64[0], np.zeros_like(g64[1])]
    assert kind._update_report(params, after, dropped, norm, **recipe)["rel_diff"] > 0.1
    assert kind._update_report(params, None, g64, norm, **recipe)["rel_diff"] == float("inf")


# ---- BENCHMARK.json against the files it names -----------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    kinds = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and len(w["why"]) <= 200
        with open(os.path.join(REPO, "benchmark", "traffic", w["traffic"] + ".json")) as f:
            kinds.add(json.load(f)["kind"])
    for kind in kinds:
        assert os.path.exists(os.path.join(REPO, "benchmark", "kinds", kind + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs), "a pair of configuration and mix may be named once"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(cells) // 4)
    for m in bench["end_to_end"]:
        assert m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]
        where = set(m.get("workloads", cells))
        assert where <= set(e2e[m["moves"]].get("workloads", cells)), m["name"]
    for cell in cells:  # every cell: setup_s, another end-to-end metric, a per-layer metric
        assert any(cell in m.get("workloads", ()) for m in bench["end_to_end"])
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])



def test_the_four_chip_mix_is_the_one_chip_mix():
    """r50-train-dp4's metric over r50-train-b8's is the scaling efficiency
    only while the two mixes offer the same work per chip."""
    mixes = []
    for name in ("train-loop-b8", "train-loop-b8-dp4"):
        with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
            mixes.append(json.load(f))
    assert mixes[0].pop("what") != mixes[1].pop("what")
    assert mixes[0] == mixes[1]
