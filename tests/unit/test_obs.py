"""Observability subsystem tests (ISSUE 3): trace spans, Chrome export,
event sink run headers + NaN passthrough, stall watchdog, spawn-site audit.

Deliberately jax-light: the obs core must work in jax-free processes (shm
decode workers trace their decodes), so nothing here compiles a program.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.obs import events as events_lib
from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.obs import watchdog as watchdog_lib
from batchai_retinanet_horovod_coco_tpu.obs.events import (
    EventSink,
    scalarize,
    split_runs,
)


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts and ends with tracing disabled (module-global),
    and runs without a profiler-annotation factory: another test file in
    this process may have imported train/loop.py, which installs one."""
    installed = trace.annotation_factory()
    trace.install_annotation_factory(None)
    trace.reset()
    yield installed
    trace.reset()
    if installed is None and "batchai_retinanet_horovod_coco_tpu.train.loop" in sys.modules:
        import jax  # the test imported train/loop.py first: keep what that installed

        installed = jax.profiler.TraceAnnotation
    trace.install_annotation_factory(installed)


def _load_trace(path):
    with open(path) as f:
        doc = json.load(f)
    assert "traceEvents" in doc
    return doc


def _validate_chrome_schema(doc):
    """The subset of the trace_event contract Perfetto relies on."""
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "C", "M"), ev
        assert "pid" in ev and "name" in ev, ev
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], int) and isinstance(ev["dur"], int)
            assert ev["dur"] >= 0 and "tid" in ev
        elif ev["ph"] == "C":
            assert "value" in ev["args"]
        elif ev["ph"] == "M":
            assert ev["name"] in (
                "process_name", "thread_name", "process_labels"
            )


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what it was
    made with and counts how often it was entered and left, and where."""

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs
        self.entered = self.exited = 0
        self.exit_thread = None

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.exited += 1
        self.exit_thread = threading.get_ident()
        return False


def _install_recording_factory() -> list:
    made = []

    def factory(name, **kwargs):
        made.append(_RecordingAnnotation(name, **kwargs))
        return made[-1]

    trace.install_annotation_factory(factory)
    return made


class TestTrace:
    def test_disabled_mode_is_a_shared_noop(self, tmp_path):
        # No configure(): span() must return the one null singleton (no
        # allocation on the hot path), record nothing, export nothing.
        assert trace.span("a") is trace.span("b")
        with trace.span("ignored"):
            pass
        trace.instant("ignored")
        trace.counter("ignored", 1.0)
        trace.end(trace.begin("ignored"))  # begin() -> None, end(None) ok
        assert trace.export() is None
        assert not trace.enabled()

    def test_span_nesting_and_schema(self, tmp_path):
        trace.configure(str(tmp_path), process_label="t")
        with trace.span("outer", step=1):
            with trace.span("inner"):
                time.sleep(0.002)
        doc = _load_trace(trace.export())
        _validate_chrome_schema(doc)
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        outer, inner = spans["outer"], spans["inner"]
        assert outer["args"] == {"step": 1}
        # Same thread, inner contained within outer.
        assert inner["tid"] == outer["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    def test_cross_thread_begin_end_parity(self, tmp_path):
        trace.configure(str(tmp_path), process_label="t")
        with trace.span("same_thread"):
            time.sleep(0.002)
        handle = trace.begin("cross_thread")
        t = threading.Thread(
            target=lambda: (time.sleep(0.002), trace.end(handle))
        )
        t.start()
        t.join()
        doc = _load_trace(trace.export())
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        cross, same = spans["cross_thread"], spans["same_thread"]
        # The cross-thread span lands on the BEGINNING thread's track and
        # measures begin->end like an in-thread span does.
        assert cross["tid"] == same["tid"]
        assert cross["dur"] >= int(0.002 * 1e6)

    def test_distinct_threads_distinct_tracks(self, tmp_path):
        trace.configure(str(tmp_path), process_label="t")

        def worker():
            with trace.span("worker_span"):
                pass

        t = threading.Thread(target=worker, name="obs-test-worker")
        t.start()
        t.join()
        with trace.span("main_span"):
            pass
        doc = _load_trace(trace.export())
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert spans["worker_span"]["tid"] != spans["main_span"]["tid"]
        thread_names = {
            e["args"]["name"]
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "obs-test-worker" in thread_names

    def test_ring_capacity_drops_oldest(self, tmp_path):
        trace.configure(str(tmp_path), capacity=10, process_label="t")
        for i in range(25):
            trace.instant(f"ev{i}")
        doc = _load_trace(trace.export())
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "i"]
        assert len(names) == 10
        assert names == [f"ev{i}" for i in range(15, 25)]  # tail survives
        assert doc["otherData"]["events_dropped_by_ring"] == 15

    def test_merge_traces_combines_processes_same_run_only(self, tmp_path):
        # A stale partial from a PREVIOUS run sharing the obs dir: pids
        # recycle across runs, so only the run-id prefix can exclude it.
        stale = tmp_path / "trace-deadbeef-train-99999.json"
        stale.write_text(json.dumps({"traceEvents": [
            {"ph": "i", "name": "stale_span", "ts": 0, "s": "t",
             "pid": 99999, "tid": 1},
        ]}))
        # Simulate two processes OF THIS RUN via two explicit exports.
        trace.configure(str(tmp_path), process_label="a")
        with trace.span("span_a"):
            pass
        trace.export()
        trace.export(
            os.path.join(
                str(tmp_path), f"trace-{trace.run_id()}-b-99999.json"
            )
        )
        merged = trace.merge_traces(str(tmp_path))
        doc = _load_trace(merged)
        _validate_chrome_schema(doc)
        assert len(doc["otherData"]["merged_from"]) == 2
        assert [e for e in doc["traceEvents"] if e["name"] == "span_a"]
        assert not [
            e for e in doc["traceEvents"] if e["name"] == "stale_span"
        ]

    def test_reset_invalidates_other_threads_rings(self, tmp_path):
        # A long-lived thread surviving a reset()+reconfigure must have
        # its events land in the NEW registry, not an orphaned ring.
        trace.configure(str(tmp_path), process_label="t")
        go = threading.Event()
        done = threading.Event()

        def long_lived():
            with trace.span("before_reset"):
                pass
            go.wait(5)
            with trace.span("after_reset"):
                pass
            done.set()

        t = threading.Thread(target=long_lived)
        t.start()
        while not any(r.thread_name == t.name for r in trace._rings):
            time.sleep(0.005)
        trace.reset()
        trace.configure(str(tmp_path), process_label="t2")
        go.set()
        assert done.wait(5)
        t.join()
        doc = _load_trace(trace.export())
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
        assert "after_reset" in names and "before_reset" not in names

    def test_fork_inherited_state_relabels_and_drops_rings(
        self, tmp_path, monkeypatch
    ):
        # A FORK-started worker inherits _enabled plus the parent's rings;
        # re-exporting them under the child pid would duplicate every
        # pre-fork span on the merged timeline.  Simulate the child by
        # faking the recorded config pid.
        trace.configure(str(tmp_path), process_label="parent")
        with trace.span("parent_span"):
            pass
        monkeypatch.setattr(trace, "_config_pid", os.getpid() - 1)
        assert trace.maybe_configure_from_env("shm-worker-0")
        with trace.span("child_span"):
            pass
        doc = _load_trace(trace.export())
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
        assert "child_span" in names and "parent_span" not in names
        proc_names = [
            e["args"]["name"]
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        ]
        assert any("shm-worker-0" in n for n in proc_names)

    def test_no_factory_means_null_span_and_no_jax(self):
        """A process that installs no annotation factory (a decode worker)
        gets the shared null span, and importing obs/trace.py pulls in no
        jax: checked in a fresh interpreter."""
        import subprocess

        code = (
            "import sys\n"
            "from batchai_retinanet_horovod_coco_tpu.obs import trace\n"
            "assert trace.annotation_factory() is None\n"
            "assert trace.span('a') is trace.span('b', k=1)\n"
            "assert trace.begin('a') is None\n"
            "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        )
        repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=repo, capture_output=True, text=True
        )
        assert proc.returncode == 0, proc.stderr

    def test_factory_with_ring_off_is_the_annotation_alone(self):
        made = _install_recording_factory()
        with trace.span("step"):
            pass
        with trace.span("compile_train_step", bucket="64x96"):
            pass
        assert [(a.name, a.kwargs, a.entered, a.exited) for a in made] == [
            ("rn.step", {}, 1, 1),
            ("rn.compile_train_step", {"bucket": "64x96"}, 1, 1),
        ]
        # The span IS the annotation object: nothing of the ring around it.
        assert trace.span("x") is made[-1]
        assert trace.snapshot_events() == [] and not trace.enabled()

    def test_factory_with_ring_on_is_both(self, tmp_path):
        made = _install_recording_factory()
        trace.configure(str(tmp_path), process_label="t")
        with trace.span("data_wait"):
            time.sleep(0.001)
        assert [(a.name, a.entered, a.exited) for a in made] == [("rn.data_wait", 1, 1)]
        spans = [e for e in trace.snapshot_events() if e["ph"] == "X"]
        assert [e["name"] for e in spans] == ["data_wait"]  # no prefix in the ring
        assert spans[0]["dur"] >= 1000

    @pytest.mark.parametrize("ring", [False, True])
    def test_begin_end_across_threads_with_factory(self, tmp_path, ring):
        made = _install_recording_factory()
        if ring:
            trace.configure(str(tmp_path), process_label="t")
        handle = trace.begin("cross_thread", id=7)
        assert (made[0].entered, made[0].exited) == (1, 0)
        t = threading.Thread(target=trace.end, args=(handle,))
        t.start()
        t.join()
        assert [(a.name, a.kwargs, a.entered, a.exited) for a in made] == [
            ("rn.cross_thread", {"id": 7}, 1, 1)]
        assert made[0].exit_thread != threading.get_ident()
        spans = [e["name"] for e in trace.snapshot_events() if e["ph"] == "X"]
        assert spans == (["cross_thread"] if ring else [])

    def test_train_loop_installs_the_profilers_annotation(self, _clean_trace_state):
        """Importing train/loop.py is what makes spans profiler annotations
        (jax.profiler.TraceAnnotation under the rn. prefix)."""
        import jax

        from batchai_retinanet_horovod_coco_tpu.train import loop  # noqa: F401

        # Installed by this import, or by an earlier one (the fixture then
        # set it aside and yields it).
        installed = trace.annotation_factory() or _clean_trace_state
        assert installed is jax.profiler.TraceAnnotation
        assert trace.ANNOTATION_PREFIX == "rn."
        trace.install_annotation_factory(installed)
        with trace.span("step"):  # a real TraceMe, no profiler session: a flag check
            pass

    def test_monotonic_clock_alignment(self):
        t = trace.monotonic_s()
        wall = trace.to_wall(t)
        assert abs(wall - time.time()) < 1.0  # same wall timeline


class TestWatchdog:
    def test_detects_injected_stalled_consumer(self):
        w = watchdog_lib.Watchdog(stall_after=10.0)
        # Per-component budget: the "healthy" peer must stay inside its
        # (large) budget at every injected ``now`` below.
        healthy = w.register("healthy-producer", stall_after=1e6)
        stalled = w.register(
            "stalled-consumer", details=lambda: {"qsize": 4}
        )
        t0 = trace.monotonic_s()
        stalled.beat()
        healthy.beat()
        assert w.check_once(now=t0 + 1.0) is None  # nobody over budget
        healthy.beat()
        diag = w.check_once(now=trace.monotonic_s() + 11.0)
        assert diag is not None
        # The diagnosis names the right component and carries its gauges.
        assert diag["component"] == "stalled-consumer"
        by_name = {c["name"]: c for c in diag["components"]}
        assert by_name["stalled-consumer"]["details"] == {"qsize": 4}
        assert "healthy-producer" in by_name
        # One dump per stall: the same wedge does not re-fire...
        assert w.check_once(now=trace.monotonic_s() + 12.0) is None
        # ...until the component beats (recovers) and wedges again.
        stalled.beat()
        assert (
            w.check_once(now=trace.monotonic_s() + 11.0)["component"]
            == "stalled-consumer"
        )

    def test_idle_components_are_not_flagged(self):
        w = watchdog_lib.Watchdog(stall_after=0.01)
        hb = w.register("backpressured")
        hb.beat()
        hb.idle()
        assert w.check_once(now=trace.monotonic_s() + 100.0) is None
        hb.beat()  # beat clears idle
        assert (
            w.check_once(now=trace.monotonic_s() + 100.0)["component"]
            == "backpressured"
        )

    def test_poll_thread_dumps_structured_diagnosis(self, tmp_path):
        dump = tmp_path / "stacks.txt"
        stalls = []
        w = watchdog_lib.Watchdog(
            stall_after=0.05,
            poll_interval=0.02,
            dump_path=str(dump),
            on_stall=stalls.append,
        )
        hb = w.register("wedged-thread")
        hb.beat()
        w.start()
        try:
            deadline = time.monotonic() + 5.0
            while not stalls and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            w.stop()
        assert stalls and stalls[0]["component"] == "wedged-thread"
        text = dump.read_text()
        # Structured JSON line + faulthandler all-thread stacks.
        assert json.loads(text.splitlines()[0])["event"] == "watchdog_stall"
        assert "thread stacks" in text and "File " in text
        hb.close()

    def test_unregister_and_name_uniquing(self):
        w = watchdog_lib.Watchdog()
        a = w.register("eval-consumer")
        b = w.register("eval-consumer")  # repeated eval re-registers
        assert {a.name, b.name} == {"eval-consumer", "eval-consumer#2"}
        a.close()
        b.close()
        assert w.components() == {}

    def test_details_error_does_not_kill_diagnosis(self):
        w = watchdog_lib.Watchdog(stall_after=0.01)
        def boom():
            raise RuntimeError("gauge died")
        hb = w.register("flaky-gauges", details=boom)
        hb.beat()
        diag = w.check_once(now=trace.monotonic_s() + 1.0)
        assert diag["component"] == "flaky-gauges"
        assert "gauge died" in str(
            diag["components"][0]["details"]["details_error"]
        )


class TestEventSink:
    def test_run_header_and_split_runs(self, tmp_path):
        for run in range(2):
            logger = EventSink(str(tmp_path), stdout=False)
            logger.log(1 + run, {"loss": 0.5})
            logger.close()
        runs = split_runs(str(tmp_path / "metrics.jsonl"))
        assert len(runs) == 2
        for run in runs:
            assert run["header"]["event"] == "run_header"
            assert "run_id" in run["header"]
        assert runs[0]["header"]["run_id"] != runs[1]["header"]["run_id"]
        assert events_lib.metric_records(runs[1])[0]["step"] == 2

    def test_split_runs_headerless_prefix_and_corrupt_tail(self, tmp_path):
        p = tmp_path / "metrics.jsonl"
        p.write_text(
            '{"step": 1, "train/loss": 0.5}\n'      # pre-ISSUE-3 run
            '{"event": "run_header", "run_id": "x"}\n'
            '{"step": 1, "train/loss": 0.4}\n'
            '{"step": 2, "train/lo'                  # killed mid-write
        )
        runs = split_runs(str(p))
        assert len(runs) == 2
        assert runs[0]["header"] is None
        assert runs[1]["header"]["run_id"] == "x"
        assert len(runs[1]["records"]) == 1
        assert runs[1]["corrupt"]  # half-written tail kept, not fatal

    def test_nan_passes_through_loudly(self, tmp_path, capsys):
        logger = EventSink(str(tmp_path), stdout=True)
        logger.log(3, {"loss": float("nan"), "ok": 1.0})
        logger.close()
        out = capsys.readouterr().out
        assert "NON-FINITE" in out and "loss" in out
        runs = split_runs(str(tmp_path / "metrics.jsonl"))
        rec = events_lib.metric_records(runs[0])[0]
        assert np.isnan(rec["train/loss"])  # recorded, never dropped
        assert rec["train/ok"] == 1.0

    def test_noncastable_metrics_counted_not_silent(self, tmp_path):
        logger = EventSink(str(tmp_path), stdout=False)
        logger.log(1, {"loss": 1.0, "boxes": np.zeros((3, 4)), "tag": "x"})
        assert logger.dropped_metrics_total == 2
        logger.close()
        rec = events_lib.metric_records(
            split_runs(str(tmp_path / "metrics.jsonl"))[0]
        )[0]
        assert rec["dropped_metrics"] == ["boxes", "tag"]
        assert rec["train/loss"] == 1.0

    def test_scalarize_contract(self):
        scalars, dropped = scalarize(
            {"a": 1, "inf": float("inf"), "arr": np.ones(2)}
        )
        assert scalars["a"] == 1.0 and np.isinf(scalars["inf"])
        assert dropped == ["arr"]

    def test_events_and_gauges(self, tmp_path):
        sink = EventSink(str(tmp_path), stdout=False)
        sink.event("compile", target="train_step", bucket="64x64")
        sink.gauge("qsize", 3, step=7)
        sink.close()
        runs = split_runs(str(tmp_path / "metrics.jsonl"))
        events = {r["event"]: r for r in runs[0]["records"]}
        assert events["compile"]["bucket"] == "64x64"
        assert events["gauge"]["name"] == "qsize"
        assert events["gauge"]["value"] == 3.0

    def test_emit_event_concurrent_lines_never_interleave(self, tmp_path):
        """ISSUE 20 consolidation: every subsystem's structured emit goes
        through ONE serialized ``emit_event`` — 8 concurrent emitters into
        one stream must yield only whole, parseable JSONL lines (the PR 16
        interleaving class, now guarded in exactly one place)."""
        import io

        stream = io.StringIO()
        sink = EventSink(str(tmp_path), stdout=False)
        n_threads, n_each = 8, 50
        errors: list[BaseException] = []

        def emit(tid: int) -> None:
            try:
                for i in range(n_each):
                    events_lib.emit_event(
                        "serve_stats", sink=sink, file=stream,
                        tid=tid, i=i, pad="x" * 64,
                    )
            except BaseException as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=emit, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sink.close()
        assert errors == []
        lines = stream.getvalue().splitlines()
        assert len(lines) == n_threads * n_each
        seen = set()
        for line in lines:
            rec = json.loads(line)  # raises on any torn/interleaved line
            assert rec["event"] == "serve_stats"
            seen.add((rec["tid"], rec["i"]))
        assert len(seen) == n_threads * n_each  # nothing lost or doubled
        # The guarded sink got every record too.
        runs = split_runs(str(tmp_path / "metrics.jsonl"))
        recs = [r for r in runs[0]["records"]
                if r.get("event") == "serve_stats"]
        assert len(recs) == n_threads * n_each

    def test_emit_event_survives_broken_sink(self, tmp_path):
        """The parseable line is the contract; a broken sink must not
        mask it."""
        import io

        class Broken:
            def event(self, *a, **k):
                raise RuntimeError("sink down")

        stream = io.StringIO()
        events_lib.emit_event("serve_stats", sink=Broken(), file=stream,
                              n=1)
        rec = json.loads(stream.getvalue())
        assert rec == {"event": "serve_stats", "n": 1}

    def test_emit_event_stream_is_an_event_field_not_the_output(self):
        """``stream`` is a live event field (``fleet_stream_reaped`` carries
        the stream id) — it must land IN the JSON line, never be captured
        as the output file (the tier-1 regression: ``'str' object has no
        attribute 'write'``)."""
        import io

        out = io.StringIO()
        events_lib.emit_event("fleet_stream_reaped", file=out, stream="s-1")
        rec = json.loads(out.getvalue())
        assert rec == {"event": "fleet_stream_reaped", "stream": "s-1"}


class TestIntegration:
    def test_prefetch_map_traces_and_heartbeats(self, tmp_path):
        """The shared prefetch skeleton registers/beats/unregisters and its
        spans land on the feeder thread's own track."""
        from batchai_retinanet_horovod_coco_tpu.data.prefetch import (
            prefetch_map,
        )

        trace.configure(str(tmp_path), process_label="t")
        seen_during: list[bool] = []

        def transfer(x):
            seen_during.append(
                any(
                    "obs-test-prefetch" in n
                    for n in watchdog_lib.default().components()
                )
            )
            return x * 2

        out = list(
            prefetch_map(
                range(4), transfer, depth=2,
                thread_name="obs-test-prefetch",
            )
        )
        assert out == [0, 2, 4, 6]
        assert any(seen_during)  # registered while running...
        assert not any(
            "obs-test-prefetch" in n
            for n in watchdog_lib.default().components()
        )  # ...unregistered after
        doc = _load_trace(trace.export())
        spans = [
            e for e in doc["traceEvents"]
            if e["ph"] == "X" and e["name"] == "obs-test-prefetch"
        ]
        assert len(spans) == 4
        assert all(s["tid"] != threading.get_ident() for s in spans)

    def test_audit_threads_clean(self):
        """Tier-1 wiring of scripts/audit_threads.py: every spawn site in
        the package registers with the watchdog or carries a rationale."""
        import importlib.util

        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        spec = importlib.util.spec_from_file_location(
            "audit_threads", os.path.join(root, "scripts", "audit_threads.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        violations = mod.audit_package(
            os.path.join(root, "batchai_retinanet_horovod_coco_tpu")
        )
        assert violations == [], violations

    def test_audit_flags_unwatched_spawn(self, tmp_path):
        """The audit actually bites: a bare Thread() spawn is a violation,
        and either coverage form clears it."""
        import importlib.util

        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        spec = importlib.util.spec_from_file_location(
            "audit_threads", os.path.join(root, "scripts", "audit_threads.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        bad = tmp_path / "bad.py"
        bad.write_text(
            "import threading\n\n\n"
            "def go():\n"
            "    t = threading.Thread(target=print)\n"
            "    t.start()\n"
        )
        assert len(mod.audit_file(str(bad))) == 1

        ok = tmp_path / "ok.py"
        ok.write_text(
            "import threading\n\n\n"
            "def go():\n"
            "    # watchdog: registers in run() at thread start.\n"
            "    t = threading.Thread(target=print)\n"
            "    t.start()\n"
        )
        assert mod.audit_file(str(ok)) == []

        reg = tmp_path / "reg.py"
        reg.write_text(
            "import threading\n"
            "from batchai_retinanet_horovod_coco_tpu.obs import watchdog\n\n\n"
            "def go():\n"
            "    hb = watchdog.register('x')\n"
            "    t = threading.Thread(target=hb.beat)\n"
            "    t.start()\n"
        )
        assert mod.audit_file(str(reg)) == []
