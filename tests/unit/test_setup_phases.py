"""The set-up record (ISSUE 34): ``obs/trace.py``'s phases, JAX's compile
events filed as phases by ``utils/backend.py``, and the phases at the
program's own set-up boundaries.  Every phase is found by its name and its
``id``, never by its position in the list."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.data.pipeline import Batch
from batchai_retinanet_horovod_coco_tpu.models import RetinaNetConfig, build_retinanet
from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.parallel import make_mesh
from batchai_retinanet_horovod_coco_tpu.train import create_train_state, loop
from batchai_retinanet_horovod_coco_tpu.train.optim import OptimizerConfig, make_optimizer
from batchai_retinanet_horovod_coco_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JAX_PHASES = ("jit_trace", "jit_lower", "xla_compile_or_load")


@pytest.fixture(autouse=True)
def _fresh_record():
    """The worker's earlier tests have filed phases (the listeners are
    installed once a process, by ``tests/conftest.py``); each test reads its
    own."""
    factory = trace.annotation_factory()
    trace.reset()
    yield
    trace.reset()
    trace.install_annotation_factory(factory)


def _named(name, phases=None):
    return [p for p in (trace.phases() if phases is None else phases) if p.name == name]


def _beneath(phases, root):
    out, todo = [], [root.id]
    while todo:
        parent = todo.pop()
        below = [p for p in phases if p.parent == parent]
        out += below
        todo += [p.id for p in below]
    return out


# ---- obs/trace.py -----------------------------------------------------------


def test_a_phase_is_kept_with_the_ring_off_and_exported_with_it_on(tmp_path):
    assert not trace.enabled()
    with trace.phase("unit_setup", bucket="64x96") as p:
        time.sleep(0.002)
    (kept,) = _named("unit_setup")
    assert kept.args == {"bucket": "64x96"} and kept.parent is None
    assert kept.dur == p.dur >= 0.002 and kept.thread == threading.current_thread().name
    assert trace.snapshot_events() == [] and trace.export() is None  # the ring is off
    # Configured later, the exporter still writes what set-up filed before.
    trace.configure(str(tmp_path), process_label="t")
    with trace.phase("unit_child_of_nothing"):
        pass
    with open(trace.export()) as f:
        doc = json.load(f)
    events = {e["name"]: e for e in doc["traceEvents"] if e.get("cat") == "obs.phase"}
    assert set(events) == {"unit_setup", "unit_child_of_nothing"}
    e = events["unit_setup"]
    assert e["ph"] == "X" and e["dur"] >= 2000
    assert e["args"] == {"bucket": "64x96", "phase": kept.id, "parent": None}
    assert doc["otherData"]["phases_dropped"] == 0


def test_span_still_returns_the_shared_null_span_and_the_module_imports_no_jax():
    trace.install_annotation_factory(None)
    assert trace.span("step") is trace.span("data_wait")  # ring off, no factory: one shared object
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; from batchai_retinanet_horovod_coco_tpu.obs import trace\n"
         "with trace.phase('p'): trace.record_phase('q', trace.monotonic_s(), 0.0)\n"
         "assert [p.name for p in trace.phases()] == ['q', 'p']\n"
         "print('jax' in sys.modules)"],
        env=dict(os.environ, PYTHONPATH=REPO), capture_output=True, text=True, timeout=60)
    assert proc.stdout.strip() == "False", proc.stdout + proc.stderr


def test_parent_links_and_self_time_over_nested_phases():
    with trace.phase("outer") as outer:
        with trace.phase("first"):
            time.sleep(0.004)
        t0 = trace.monotonic_s()
        with trace.phase("inside_the_late_one"):
            time.sleep(0.002)
        time.sleep(0.002)
        # A listener hears of an interval when it ends: what this thread filed
        # under the same parent since it began happened inside it.
        trace.record_phase("late", t0, trace.monotonic_s() - t0, fun="f")
    by_name = {p.name: p for p in trace.phases()}
    assert by_name["outer"].parent is None
    assert by_name["first"].parent == by_name["late"].parent == by_name["outer"].id
    assert by_name["inside_the_late_one"].parent == by_name["late"].id
    assert by_name["late"].args == {"fun": "f"}
    self_s = trace.self_times()
    o, f, l, i = (by_name[n] for n in ("outer", "first", "late", "inside_the_late_one"))
    assert self_s[f.id] == f.dur and self_s[i.id] == i.dur
    assert self_s[l.id] == pytest.approx(l.dur - i.dur, abs=1e-6)
    assert self_s[o.id] == pytest.approx(o.dur - f.dur - l.dur, abs=1e-6)
    assert outer.dur == o.dur >= f.dur + l.dur


def test_two_threads_keep_their_own_stacks():
    inside = threading.Event()
    done = threading.Event()

    def worker():
        with trace.phase("on_the_worker"):
            inside.set()
            done.wait(5)

    t = threading.Thread(target=worker, name="phase-worker")
    t.start()
    assert inside.wait(5)
    # The worker's phase is open, on ITS thread: not this one's parent.
    with trace.phase("on_main"):
        trace.record_phase("filed_on_main", trace.monotonic_s(), 0.0)
    done.set()
    t.join()
    by_name = {p.name: p for p in trace.phases()}
    assert by_name["on_main"].parent is None and by_name["on_the_worker"].parent is None
    assert by_name["filed_on_main"].parent == by_name["on_main"].id
    assert by_name["on_the_worker"].thread == "phase-worker"


def test_the_list_is_bounded_and_counts_its_drops(monkeypatch):
    monkeypatch.setattr(trace, "MAX_PHASES", 5)
    for i in range(8):
        with trace.phase(f"p{i}"):
            pass
    # Set-up comes first: a full list refuses what comes later.
    assert [p.name for p in trace.phases()] == ["p0", "p1", "p2", "p3", "p4"]
    assert trace.phases_dropped() == 3
    trace.reset()
    assert trace.phases() == [] and trace.phases_dropped() == 0


def test_from_wall_is_the_inverse_of_to_wall():
    t = trace.monotonic_s()
    assert trace.from_wall(trace.to_wall(t)) == pytest.approx(t, abs=1e-6)


# ---- utils/backend.py: JAX's events -------------------------------------------


def test_a_jit_gives_its_three_phases_with_its_name():
    def toy_for_the_setup_record(x):
        return jnp.sin(x) @ x.T + jnp.tanh(x).sum()  # jnp's own jits are traced inside: one phase

    x = jnp.ones((8, 8))
    trace.reset()
    before = backend.compile_stats()
    with trace.phase("around_the_jit") as around:
        jax.jit(toy_for_the_setup_record)(x)
    phases = trace.phases()
    (outer,) = _named("around_the_jit", phases)
    mine = [p for p in _beneath(phases, outer) if "toy_for_the_setup_record" in p.args["fun"]]
    assert sorted(p.name for p in mine) == sorted(JAX_PHASES)
    by_name = {p.name: p for p in mine}
    assert by_name["xla_compile_or_load"].args["cache"] in ("hit", "miss")
    assert all(p.parent == outer.id for p in mine)
    assert sum(p.dur for p in mine) <= around.dur
    # in the order JAX does them, on the clock the phase is on
    t = [by_name[n].t0 for n in JAX_PHASES]
    assert outer.t0 <= t[0] <= t[1] <= t[2] <= outer.t0 + outer.dur
    after = backend.compile_stats()
    assert before == dict.fromkeys(before, 0)
    assert after["requests"] == after["hits"] + after["misses"] >= 1
    assert after["trace_s"] > 0 and after["lower_s"] > 0
    assert (after["compile_s"] > 0) == (after["misses"] > 0)


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.utils import backend
backend.enable_compile_cache()
def probe_fn(x):
    return jnp.cos(x) * 3 + x
jax.jit(probe_fn)(jnp.ones((4, 4)))
(p,) = [p for p in trace.phases() if p.name == "xla_compile_or_load" and "probe_fn" in p.args["fun"]]
print(json.dumps({"args": p.args, "stats": backend.compile_stats()}))
"""


def test_a_fresh_process_loads_what_the_first_compiled(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    seen = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env, capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    first, second = seen
    assert first["args"]["cache"] == "miss" and first["args"]["written"] is True
    assert "retrieval_s" not in first["args"]
    assert second["args"]["cache"] == "hit" and second["args"]["retrieval_s"] > 0
    assert first["stats"]["misses"] >= 1 and first["stats"]["compile_s"] > 0
    assert second["stats"]["misses"] == 0 and second["stats"]["compile_s"] == 0
    assert second["stats"]["hits"] == second["stats"]["requests"] == first["stats"]["requests"]
    assert second["stats"]["load_s"] >= second["args"]["retrieval_s"]


def test_enable_compile_cache_twice_installs_one_set_of_listeners():
    from jax._src import monitoring

    def ours(listeners):
        return [f for f in listeners if getattr(f, "__module__", "") == backend.__name__]

    backend.enable_compile_cache()
    backend.enable_compile_cache()
    assert len(ours(monitoring.get_event_listeners())) == 1
    assert len(ours(monitoring.get_event_duration_listeners())) == 1
    assert len(ours(monitoring.get_event_time_span_listeners())) == 1
    assert len(ours(monitoring.get_scalar_listeners())) == 1


def test_announce_devices_files_the_backend_start(capsys):
    backend.announce_devices("unit")
    assert "unit: platform=cpu" in capsys.readouterr().out
    assert len(_named("backend_init")) == 1


# ---- the program's own boundaries -----------------------------------------------

HW = (64, 64)
NUM_CLASSES = 3
BATCH = 4


def _model():
    return build_retinanet(RetinaNetConfig(
        num_classes=NUM_CLASSES, backbone="resnet_test", norm_kind="frozen_bn",
        fpn_channels=16, head_width=16, head_depth=1, dtype=jnp.float32))


def _state(model):
    tx = make_optimizer(OptimizerConfig(schedule="constant", warmup_steps=0))[0]
    return create_train_state(model, tx, (1, *HW, 3), jax.random.key(0))


def _host_batches():
    while True:
        yield Batch(images=np.zeros((BATCH, *HW, 3), np.uint8),
                    gt_boxes=np.tile(np.asarray([[8.0, 8.0, 40.0, 40.0]], np.float32), (BATCH, 2, 1)),
                    gt_labels=np.ones((BATCH, 2), np.int32), gt_mask=np.ones((BATCH, 2), bool),
                    image_ids=np.arange(BATCH, dtype=np.int64), scales=np.ones((BATCH,), np.float32),
                    valid=np.ones((BATCH,), bool))


class _Log:
    def __init__(self):
        self.events = []

    def log(self, *a, **kw):
        pass

    def event(self, name, **fields):
        self.events.append((name, fields))


def test_create_train_state_is_a_phase_over_its_jitted_init():
    _state(_model())
    phases = trace.phases()
    (init,) = _named("init_state", phases)
    under = _beneath(phases, init)
    assert any(p.name == "jit_trace" and p.args["fun"] == "init" for p in under), [p.args for p in under]
    assert {"jit_lower", "xla_compile_or_load"} <= {p.name for p in under}
    assert sum(p.dur for p in under if p.parent == init.id) <= init.dur


def test_two_run_training_calls_give_two_builds_each_holding_the_steps_jax_phases(tmp_path):
    model = _model()
    state = _state(model)
    built_before = dict(loop._step_builds)
    trace.configure(str(tmp_path), process_label="t")
    log = _Log()
    state = loop.run_training(model, state, _host_batches(), NUM_CLASSES,
                              loop.LoopConfig(total_steps=3, log_every=0), logger=log)
    loop.run_training(model, state, _host_batches(), NUM_CLASSES,
                      loop.LoopConfig(total_steps=5, log_every=0), logger=log)
    phases = trace.phases()
    builds = sorted(_named("compile_train_step", phases), key=lambda p: p.t0)
    n = built_before.get("64x64", 0)
    assert [p.args for p in builds] == [{"bucket": "64x64", "call": n + 1}, {"bucket": "64x64", "call": n + 2}]
    for b in builds:
        assert b.parent is None
        under = _beneath(phases, b)
        step = [p for p in under if "train_step" in p.args.get("fun", "")]
        assert sorted(p.name for p in step) == sorted(JAX_PHASES), [(p.name, p.args) for p in under]
        # ... and lasts at least as long as they do
        assert b.dur >= sum(p.dur for p in under if p.parent == b.id)
    # The first call of a newly built step is the tail of its phase, no
    # ``step`` span: 5 steps ran, 2 of them builds.
    events = trace.snapshot_events()
    assert len([e for e in events if e["name"] == "step"]) == 3
    exported = [e for e in events if e["name"] == "compile_train_step"]
    assert sorted(e["args"]["call"] for e in exported) == [n + 1, n + 2]
    # What the loop reports of a compile is the phase's length.
    compiles = [f for name, f in log.events if name == "compile"]
    assert [f["build_s"] for f in compiles] == [round(b.dur, 3) for b in builds]
    assert all(f["build_s"] > 0.01 for f in compiles)


def test_on_a_mesh_the_state_is_placed_under_a_phase():
    model = _model()
    state = _state(model)
    mesh = make_mesh(4)
    loop.run_training(model, state, _host_batches(), NUM_CLASSES,
                      loop.LoopConfig(total_steps=1, log_every=0), mesh=mesh)
    (placed,) = _named("place_state")
    assert placed.args == {"devices": 4} and placed.dur > 0
    (build,) = _named("compile_train_step")
    assert placed.t0 + placed.dur <= build.t0


def test_the_compile_gauge_reports_the_real_build():
    from batchai_retinanet_horovod_coco_tpu.obs import telemetry

    telemetry.reset()
    telemetry.enable()
    try:
        model = _model()
        loop.run_training(model, _state(model), _host_batches(), NUM_CLASSES,
                          loop.LoopConfig(total_steps=2, log_every=0))
        (build,) = _named("compile_train_step")
        assert telemetry.default().snapshot()["train_last_compile_s"] == round(build.dur, 3) > 0.01
    finally:
        telemetry.reset()


def test_the_perf_doctor_books_the_compile_under_compile_and_tabulates_the_setup(tmp_path):
    """A one-process run: the step's trace, lowering and load or compile
    happen in its first call, which is the tail of ``compile_train_step``
    and no ``step`` sample."""
    from batchai_retinanet_horovod_coco_tpu.obs.analyze.report import analyze_events, validate_report

    model = _model()
    state = _state(model)
    trace.configure(str(tmp_path), process_label="t")
    loop.run_training(model, state, _host_batches(), NUM_CLASSES, loop.LoopConfig(total_steps=6, log_every=0))
    report = analyze_events(trace.snapshot_events())
    assert validate_report(report) == []
    (build,) = _named("compile_train_step")
    steps = report["steps"]
    assert steps["count"] == 5 and steps["totals_s"]["compile"] == pytest.approx(build.dur, abs=1e-3)
    assert steps["decomposition"]["compile"] > 0.05
    assert report["span_stats"]["step"]["max_ms"] < build.dur * 1e3
    assert report["span_stats"]["compile_train_step"]["count"] == 1
    # the set-up table: phase, self seconds, hit or miss
    setup = report["setup"]
    assert setup["available"] and {"init_state", "compile_train_step", *JAX_PHASES} <= set(setup["by_phase"])
    programs = setup["by_phase"]["xla_compile_or_load"]
    assert programs["hits"] + programs["misses"] == programs["count"] >= 2  # the init and the step
    (row,) = [r for r in setup["rows"] if r["phase"] == "compile_train_step"]
    assert row["what"] == "64x64" and 0 <= row["self_s"] < row["dur_s"] == pytest.approx(build.dur, abs=1e-3)
    step_program = [r for r in setup["rows"] if r["phase"] == "xla_compile_or_load" and "train_step" in r["what"]]
    assert len(step_program) == 1 and step_program[0]["cache"] in ("hit", "miss")
