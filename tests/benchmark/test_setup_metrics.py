"""The eight set-up metrics (ISSUE 34): each reader on a synthetic phase list,
the rule of what a reader returns (``harness/setup_phases.py``: no record,
a broken record, a sound record with nothing to sum), and their entries in
``BENCHMARK.json``."""

import collections
import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as run_lib  # noqa: E402
from benchmark.harness import setup_phases  # noqa: E402

Phase = collections.namedtuple("Phase", "id parent name t0 dur args thread")
T_OPEN = 100.0
METRICS = ("setup.trace_lower_s", "setup.cache_load_s", "setup.backend_compile_s", "setup.cache_misses",
           "setup.step_builds", "setup.step_build_s", "setup.init_state_s", "setup.place_state_s")


def _p(id, parent, name, t0, dur, **args):
    return Phase(id, parent, name, t0, dur, args or None, "MainThread")


def _run():
    """A warm call that compiled its step and a measured call that loaded
    it, on a mesh, with a small program before and one more compile after
    the window opened; filed in the order phases END (children first)."""
    return [
        # the state: the program's init under the benchmark's own jit
        _p(2, 3, "init_state", 1.5, 2.0),
        _p(3, None, "jit_trace", 1.0, 3.0, fun="create_train_state"),
        _p(4, None, "jit_lower", 4.0, 1.0, fun="jit(create_train_state)"),
        _p(5, None, "xla_compile_or_load", 5.0, 0.5, fun="jit(create_train_state)", cache="hit", retrieval_s=0.4),
        _p(6, None, "place_state", 10.0, 0.25, devices=4),
        # the warm call's build: traced, lowered (a kernel's jit traced inside the lowering), compiled
        _p(8, 7, "jit_trace", 11.0, 4.0, fun="sharded_step"),
        _p(9, 10, "jit_trace", 15.5, 0.5, fun="assign_fused"),
        _p(10, 7, "jit_lower", 15.0, 2.0, fun="jit(sharded_step)"),
        _p(11, 7, "xla_compile_or_load", 17.0, 30.0, fun="jit(sharded_step)", cache="miss", written=True),
        _p(7, None, "compile_train_step", 10.5, 38.0, bucket="800x1344", call=1),
        _p(12, None, "place_state", 60.0, 0.125, devices=4),
        # the measured call's: traced and lowered again, loaded
        _p(14, 13, "jit_trace", 61.0, 3.0, fun="sharded_step"),
        _p(15, 13, "jit_lower", 64.0, 1.0, fun="jit(sharded_step)"),
        _p(16, 13, "xla_compile_or_load", 65.0, 2.0, fun="jit(sharded_step)", cache="hit", retrieval_s=1.75),
        _p(13, None, "compile_train_step", 60.5, 8.0, bucket="800x1344", call=2),
        # inside the window: none of set-up's
        _p(17, None, "jit_trace", 101.0, 1.0, fun="checksum"),
        _p(18, None, "xla_compile_or_load", 103.0, 9.0, fun="jit(checksum)", cache="miss"),
        _p(19, None, "init_state", 120.0, 5.0),
    ]


EXPECTED = {
    # jit_trace 3.0 - init_state 2.0, jit_lower 1.0; 4.0; 2.0 - 0.5 and the 0.5 inside it; 3.0; 1.0
    "setup.trace_lower_s": (3.0 - 2.0) + 1.0 + 4.0 + (2.0 - 0.5) + 0.5 + 3.0 + 1.0,
    "setup.cache_load_s": 0.4 + 1.75,
    "setup.backend_compile_s": 30.0,
    "setup.cache_misses": 1.0,
    "setup.step_builds": 2.0,
    "setup.step_build_s": 38.0 + 8.0,
    "setup.init_state_s": 2.0,
    "setup.place_state_s": 0.25 + 0.125,
}


def _ctx(t_open=T_OPEN):
    return types.SimpleNamespace(run=types.SimpleNamespace(t_open=t_open))


def _program_keeps(monkeypatch, phases):
    monkeypatch.setattr(setup_phases, "program_phases", lambda: phases)


@pytest.mark.parametrize("metric", METRICS)
def test_each_reader_on_a_synthetic_phase_list(monkeypatch, capsys, metric):
    _program_keeps(monkeypatch, _run())
    assert tuple(setup_phases.METRICS) == METRICS
    value = run_lib.load_module("layer_metrics", metric).read(_ctx())
    assert value == pytest.approx(EXPECTED[metric], abs=1e-9) and isinstance(value, float)
    # the table behind the values is printed once a run, whoever asks first
    (said,) = [l for l in capsys.readouterr().out.splitlines() if l.startswith("benchmark: setup_phases {")]
    table = json.loads(said[len("benchmark: setup_phases "):])
    assert table["values"][metric] == value
    assert table["phases_before_window"] == 15 and table["phases_after"] == 3


def test_phases_after_the_window_opened_are_left_out(monkeypatch):
    _program_keeps(monkeypatch, _run())
    early = setup_phases.values(_ctx(t_open=60.2))  # the measured call has not built its step yet
    assert early["setup.step_builds"] == 1.0 and early["setup.step_build_s"] == 38.0
    assert early["setup.place_state_s"] == 0.375 and early["setup.cache_load_s"] == 0.4
    late = setup_phases.values(_ctx(t_open=1000.0))  # ... and a window that never opened before the end sees all
    assert late["setup.cache_misses"] == 2.0 and late["setup.backend_compile_s"] == 39.0
    assert late["setup.init_state_s"] == 7.0


def test_the_table_splits_each_build_into_trace_lower_load_and_the_rest(monkeypatch):
    table = setup_phases.reduce(_run(), T_OPEN)["table"]
    first, second = table["step_builds"]
    assert (first["call"], first["bucket"], first["cache"]) == (1, "800x1344", ["miss"])
    assert (first["jit_trace_s"], first["jit_lower_s"], first["xla_compile_or_load_s"]) == (4.5, 1.5, 30.0)
    assert first["rest_s"] == pytest.approx(38.0 - 4.0 - 2.0 - 30.0)
    assert (second["call"], second["cache"], second["rest_s"]) == (2, ["hit"], 2.0)
    assert table["by_name"]["compile_train_step"] == {"n": 2, "dur_s": 46.0, "self_s": 4.0}
    assert table["longest"][0] == ["xla_compile_or_load", "jit(sharded_step)", 30.0, "miss"]


def test_rule_a_a_program_without_a_phase_record_reads_zero_and_says_so_once(monkeypatch, capsys):
    """The parent of the PR that brought the metrics (and every program
    before it): ``obs/trace.py`` has no ``phases``."""
    from batchai_retinanet_horovod_coco_tpu.obs import trace

    monkeypatch.delattr(trace, "phases")
    ctx = _ctx()
    got = {m: run_lib.load_module("layer_metrics", m).read(ctx) for m in METRICS}
    assert got == dict.fromkeys(METRICS, 0.0)
    out = capsys.readouterr().out
    assert out.count("benchmark: setup_phases: this program keeps no phase record; the set-up metrics read 0") == 1
    assert "benchmark: setup_phases {" not in out


@pytest.mark.parametrize("phases,why", [
    # the record is there and the loop's phase is not
    ([p for p in _run() if p.name != "compile_train_step"], "no compile_train_step phase began before the window"),
    # the loop's phases are there and JAX's listeners were never installed
    ([p for p in _run() if p.name in ("compile_train_step", "place_state", "init_state")],
     "no compile_train_step phase holds a trace, a lowering or a compile"),
    ([], "no compile_train_step phase began before the window"),
])
def test_rule_b_a_broken_record_reads_nothing_and_the_run_is_not_correct_by_name(monkeypatch, capsys, phases, why):
    _program_keeps(monkeypatch, phases)
    ctx = _ctx()
    values = {m: run_lib.load_module("layer_metrics", m).read(ctx) for m in METRICS}
    assert values == dict.fromkeys(METRICS)
    assert f"benchmark: setup_phases: the program's phase record is broken: {why}" in capsys.readouterr().out
    # what run.py::main makes of a listed metric whose reader found nothing
    problems = [f"per-layer metric {name} is listed for this cell and its reader found nothing to read"
                for name, value in values.items() if value is None]
    assert [p.split()[2] for p in problems] == list(METRICS)


def test_rule_c_a_sum_over_no_matching_phase_in_a_sound_record_is_zero(monkeypatch):
    """One chip, a warm machine, the step kept across calls: no mesh, no
    miss, and a second build with nothing of JAX's beneath it."""
    sound = [p for p in _run() if p.name not in ("place_state", "init_state") and p.id not in (14, 15, 16)]
    sound = [p._replace(args=dict(p.args, cache="hit", retrieval_s=1.0)) if p.id == 11 else p for p in sound]
    values = setup_phases.reduce(sound, T_OPEN)
    assert values["setup.place_state_s"] == values["setup.init_state_s"] == 0.0
    assert values["setup.cache_misses"] == values["setup.backend_compile_s"] == 0.0
    assert values["setup.step_builds"] == 2.0 and values["setup.cache_load_s"] == 1.4
    assert "broken" not in values["table"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_eight_entries_are_appended_for_the_four_chip_cell_alone(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    counters = {"setup.cache_misses", "setup.step_builds"}
    for name in METRICS:
        assert entries[name] == {
            "name": name, "unit": "count" if name in counters else "s", "better": "lower",
            "source": "program_counter" if name in counters else "program_span",
            "layer": "entry points and set-up", "moves": "setup_s", "workloads": ["r50-train-dp4"]}
        assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", name + ".py"))
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("nemo_ssd_roofline") + 1 == min(names.index(n) for n in METRICS)  # after what was there
    assert entries["setup.compiles_in_window"]["layer"] == "entry points and set-up"


ACCEPTED = ["setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.device_ms", "train_step.mfu_pct"]


@pytest.mark.parametrize("cell,more", [
    ("r50-train-b8", ["assign_fused.kernel_ms", "assign_fused_roofline"]),
    ("r50-train-b8-portrait", ["assign_fused.kernel_ms", "assign_fused_roofline"]),
    ("granite-h-train-pack8k", ["lm_step.mamba_ms", "lm_step.ssd_ms", "lm_step.attention_ms", "lm_step.mlp_ms"]),
    ("dsv2-lite-train-pack8k", ["moe_step.mla_ms", "moe_step.router_ms", "moe_step.experts_ms", "moe_step.shared_ms",
                                "moe_gmm_roofline", "moe_step.mla_core_ms"]),
    ("nemo3-nano-train-pack8k", ["nemo_step.mamba_ms", "nemo_step.ssd_ms", "nemo_step.attention_ms",
                                 "nemo_step.router_ms", "nemo_step.experts_ms", "nemo_step.shared_ms",
                                 "nemo_gmm_roofline", "nemo_ssd_roofline"]),
])
def test_the_other_cells_listed_sets_are_what_they_were(bench, cell, more):
    """Four accepted tests pin these cells' exact sets, so the set-up
    metrics are listed for ``r50-train-dp4`` alone and read by hand here
    (``python3 -m benchmark.harness.setup_phases``)."""
    listed = [m["name"] for m in run_lib.metrics_for(bench["per_layer"], cell)]
    assert listed == ACCEPTED + more
    dp4 = [m["name"] for m in run_lib.metrics_for(bench["per_layer"], "r50-train-dp4")]
    assert dp4 == ["setup.compiles_in_window", "train_loop.data_wait_ms", "train_step.mfu_pct", *METRICS]
