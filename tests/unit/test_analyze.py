"""Perf doctor tests (ISSUE 8, obs/analyze): committed-fixture golden
output (bit-for-bit, inline == offline CLI), report schema validation,
robustness on corrupt/legacy/empty artifacts, the shared percentile
helper's equivalence pin, the watchdog stall trace marker, span
attribution over live rings.

The fixture (tests/fixtures/perf_doctor/) is a real CPU train+eval smoke
recording: trace.json + metrics.jsonl as `--obs-trace` left them, plus
PERF_REPORT.golden.json — the analyzer's committed output for exactly
those artifacts.  jax-free, like the analyzer itself.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.obs import trace
from batchai_retinanet_horovod_coco_tpu.obs import watchdog as watchdog_lib
from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
    AnalyzeError,
    analyze_dir,
    analyze_events,
    auto_emit,
    device_peak_tflops,
    span_attribution,
    validate_report,
    write_report,
)
from batchai_retinanet_horovod_coco_tpu.obs.analyze.__main__ import main as cli_main
from batchai_retinanet_horovod_coco_tpu.obs.events import latency_percentiles

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures",
    "perf_doctor",
)
GOLDEN = os.path.join(FIXTURE, "PERF_REPORT.golden.json")


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.reset()
    yield
    trace.reset()


def _golden_bytes() -> bytes:
    with open(GOLDEN, "rb") as f:
        return f.read()


class TestGoldenFixture:
    def test_analyze_dir_reproduces_golden_bit_for_bit(self, tmp_path):
        report = analyze_dir(FIXTURE)
        out = write_report(report, str(tmp_path / "PERF_REPORT.json"))
        with open(out, "rb") as f:
            assert f.read() == _golden_bytes()

    def test_cli_reproduces_golden_bit_for_bit(self, tmp_path, capsys):
        out = str(tmp_path / "PERF_REPORT.json")
        assert cli_main([FIXTURE, "--out", out]) == 0
        with open(out, "rb") as f:
            assert f.read() == _golden_bytes()
        # The CLI prints a one-line machine-readable summary.
        summary = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert summary["perf_report"] == out
        assert summary["top_bottlenecks"]

    def test_golden_satisfies_the_acceptance_properties(self):
        """The acceptance criteria, pinned on the committed recording: a
        schema-valid report with decomposition summing to ~1, an eval
        overlap ratio, cost-analysis-derived achieved FLOP/s (and NO
        utilization: the recording is a CPU run, and no peak is assumed
        for a device the table does not know), and a non-empty ranked
        top-3 verdict."""
        report = json.loads(_golden_bytes())
        assert validate_report(report) == []
        d = report["steps"]["decomposition"]
        assert abs(sum(d.values()) - 1.0) < 0.02
        assert set(d) == {
            "data_wait", "compile", "step", "metrics_fetch", "eval", "other"
        }
        ev = report["pipeline"]["eval"]
        assert 0.0 <= ev["overlap_efficiency"] <= 1.0
        assert ev["batches"] > 0
        mfu = report["mfu"]
        assert mfu["flops_source"] == "trace_cost_analysis"
        assert mfu["flops_per_step"] > 0
        assert mfu["achieved_tflops"] > 0
        assert mfu["peak_tflops"] is None and mfu["mfu"] is None
        assert 1 <= len(report["bottlenecks"]) <= 3
        assert [b["rank"] for b in report["bottlenecks"]] == list(
            range(1, len(report["bottlenecks"]) + 1)
        )
        assert all(b["spans"] for b in report["bottlenecks"])

    def test_stall_correlation_present_for_feed_queue(self):
        report = json.loads(_golden_bytes())
        q = report["queues"]["device-prefetch.qsize"]
        assert "starved_data_wait_fraction" in q
        assert 0.0 <= q["starved_data_wait_fraction"] <= 1.0


class TestValidation:
    def test_golden_valid_and_mutations_bite(self):
        report = json.loads(_golden_bytes())
        assert validate_report(report) == []

        bad = json.loads(_golden_bytes())
        bad["schema_version"] = 99
        assert any("schema_version" in p for p in validate_report(bad))

        bad = json.loads(_golden_bytes())
        bad["steps"]["decomposition"]["other"] += 0.1  # breaks the sum
        assert any("sums to" in p for p in validate_report(bad))

        bad = json.loads(_golden_bytes())
        bad["steps"]["decomposition"]["step"] = 1.5  # out of range
        assert any("out of [0,1]" in p for p in validate_report(bad))

        bad = json.loads(_golden_bytes())
        bad["bottlenecks"][0]["rank"] = 7
        assert any("rank" in p for p in validate_report(bad))

        bad = json.loads(_golden_bytes())
        del bad["mfu"]
        assert any("mfu" in p for p in validate_report(bad))

        assert validate_report("not a dict") == ["report is not an object"]


class TestRobustness:
    def test_missing_trace_raises_clean_error_and_cli_exits_2(
        self, tmp_path, capsys
    ):
        with pytest.raises(AnalyzeError, match="cannot read trace"):
            analyze_dir(str(tmp_path))
        assert cli_main([str(tmp_path)]) == 2
        assert "run a traced workload" in capsys.readouterr().err

    def test_invalid_json_trace(self, tmp_path):
        (tmp_path / "trace.json").write_text("{half a trace")
        with pytest.raises(AnalyzeError, match="not valid JSON"):
            analyze_dir(str(tmp_path))

    def test_empty_trace_degrades_without_crashing(self, tmp_path):
        (tmp_path / "trace.json").write_text(json.dumps({"traceEvents": []}))
        report = analyze_dir(str(tmp_path))
        assert report["steps"] is None
        assert report["bottlenecks"] == []
        assert report["memory"] == {"available": False}
        assert report["mfu"]["mfu"] is None

    def test_headerless_legacy_and_corrupt_tail_events(self, tmp_path):
        """The split_runs robustness cases, through the analyzer: a
        pre-ISSUE-3 headerless prefix and a half-written tail must show
        up as counts, never as a crash."""
        (tmp_path / "trace.json").write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {"ph": "X", "name": "step", "ts": 0, "dur": 100,
                         "pid": 1, "tid": 1},
                        {"ph": "X", "name": "data_wait", "ts": 100,
                         "dur": 10, "pid": 1, "tid": 1},
                    ]
                }
            )
        )
        (tmp_path / "metrics.jsonl").write_text(
            '{"step": 1, "train/loss": 0.5}\n'  # headerless legacy run
            '{"step": 2, "train/lo'  # killed mid-write
        )
        report = analyze_dir(str(tmp_path))
        ev = report["events"]
        assert ev["available"] is True
        assert ev["corrupt_lines"] == 1
        assert ev["header"]["device_kind"] is None
        assert report["steps"]["count"] == 1
        assert report["bottlenecks"]  # still ranks from what it has

    def test_events_name_none_skips_a_stale_jsonl(self, tmp_path):
        """A shared obs dir can hold a PREVIOUS train run's
        metrics.jsonl, and events_name=None keeps its header/compile
        records out of this trace's report."""
        (tmp_path / "trace.json").write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {"ph": "X", "name": "detect_fetch", "ts": 0,
                         "dur": 50, "pid": 1, "tid": 1}
                    ]
                }
            )
        )
        (tmp_path / "metrics.jsonl").write_text(
            '{"event": "run_header", "run_id": "stale", '
            '"device_kind": "TPU v5 lite"}\n'
            '{"event": "compile", "build_s": 99.0}\n'
        )
        with_events = analyze_dir(str(tmp_path))
        assert with_events["events"]["available"] is True
        skipped = analyze_dir(str(tmp_path), events_name=None)
        assert skipped["events"] == {"available": False}
        assert skipped["source"]["device_kind"] is None

    def test_no_events_jsonl_is_fine(self, tmp_path):
        (tmp_path / "trace.json").write_text(
            json.dumps(
                {
                    "traceEvents": [
                        {"ph": "X", "name": "step", "ts": 0, "dur": 50,
                         "pid": 1, "tid": 1}
                    ]
                }
            )
        )
        report = analyze_dir(str(tmp_path))
        assert report["events"] == {"available": False}
        assert report["source"]["events"] is False

    def test_auto_emit_never_raises(self, tmp_path, capsys):
        assert auto_emit(str(tmp_path / "nope")) is None
        err = capsys.readouterr().err
        line = json.loads(err.splitlines()[-1])
        assert line["event"] == "perf_report_error"

        class Sink:
            def __init__(self):
                self.events = []

            def event(self, kind, **fields):
                self.events.append((kind, fields))

        sink = Sink()
        assert auto_emit(str(tmp_path / "nope"), sink=sink) is None
        assert sink.events[0][0] == "perf_report_error"


class TestCheckMode:
    def test_identical_reports_pass(self, tmp_path, capsys):
        assert cli_main([FIXTURE, "--out", str(tmp_path / "r.json"),
                         "--check", GOLDEN]) == 0
        out = capsys.readouterr().out
        assert "REGRESSION" not in out

    def test_fraction_regression_fails(self, tmp_path, capsys):
        baseline = json.loads(_golden_bytes())
        d = baseline["steps"]["decomposition"]
        # Invert the attribution: the committed world spent its window in
        # data_wait — a fresh report matching the fixture is > band away.
        d["data_wait"], d["step"] = d["step"], d["data_wait"]
        bpath = tmp_path / "baseline.json"
        bpath.write_text(json.dumps(baseline))
        assert cli_main([FIXTURE, "--out", str(tmp_path / "r.json"),
                         "--check", str(bpath)]) == 1
        assert "REGRESSION" in capsys.readouterr().out

    def test_device_class_mismatch_passes_loudly(self, tmp_path, capsys):
        baseline = json.loads(_golden_bytes())
        baseline["source"]["device_kind"] = "TPU v5 lite"
        bpath = tmp_path / "baseline.json"
        bpath.write_text(json.dumps(baseline))
        assert cli_main([FIXTURE, "--out", str(tmp_path / "r.json"),
                         "--check", str(bpath)]) == 0
        assert "not comparable across device classes" in (
            capsys.readouterr().out
        )

    def test_unreadable_baseline_fails(self, tmp_path, capsys):
        assert cli_main([FIXTURE, "--out", str(tmp_path / "r.json"),
                         "--check", str(tmp_path / "missing.json")]) == 1
        assert "cannot read baseline" in capsys.readouterr().out


class TestPercentileHelper:
    def test_matches_numpy_reference(self):
        """Satellite pin: the ONE helper computes exactly the quantiles
        the two former inline implementations computed."""
        rng = np.random.default_rng(0)
        samples = rng.exponential(20.0, size=257).tolist()
        out = latency_percentiles(samples)
        assert out["count"] == 257
        for p in (50, 90, 99):
            assert out[f"p{p}_ms"] == round(
                float(np.percentile(np.asarray(samples), p)), 3
            )
        assert out["mean_ms"] == round(float(np.mean(samples)), 3)
        assert out["max_ms"] == round(float(np.max(samples)), 3)
        assert latency_percentiles([]) == {}

    def test_serve_snapshot_equivalence(self):
        """LatencyStats.snapshot's p50/p99 are the shared helper's numbers
        (reuse, not a clone — the satellite's point)."""
        from batchai_retinanet_horovod_coco_tpu.serve.common import (
            LatencyStats,
        )

        rng = np.random.default_rng(1)
        stats = LatencyStats(window=4096)
        samples_s = rng.exponential(0.02, size=100).tolist()
        for s in samples_s:
            stats.record(s)
        snap = stats.snapshot()
        ref = latency_percentiles(
            [s * 1e3 for s in samples_s], ps=(50, 99)
        )
        assert snap["p50_ms"] == ref["p50_ms"]
        assert snap["p99_ms"] == ref["p99_ms"]
        assert snap["mean_ms"] == ref["mean_ms"]
        assert snap["max_ms"] == ref["max_ms"]
        assert snap["window"] == ref["count"]

    def test_histogram_record_uses_helper(self, tmp_path):
        from batchai_retinanet_horovod_coco_tpu.obs.events import (
            EventSink,
            split_runs,
        )

        sink = EventSink(str(tmp_path), stdout=False)
        sink.histogram("lat", [1.0, 2.0, 3.0, 10.0])
        sink.close()
        rec = [
            r
            for r in split_runs(str(tmp_path / "metrics.jsonl"))[0]["records"]
            if r.get("event") == "histogram"
        ][0]
        ref = latency_percentiles([1.0, 2.0, 3.0, 10.0])
        for k, v in ref.items():
            assert rec[k] == v


class TestStallMarker:
    def test_watchdog_dump_emits_trace_instant(self, tmp_path):
        """Satellite: a stall diagnosis is visible ON the Perfetto
        timeline (trace.instant), not only in JSONL/stacks — and the
        analyzer reads it back into the stalls section."""
        trace.configure(str(tmp_path), process_label="t")
        w = watchdog_lib.Watchdog(
            stall_after=0.01, dump_path=str(tmp_path / "stacks.txt")
        )
        hb = w.register("wedged-component")
        hb.beat()
        diag = w.check_once(now=trace.monotonic_s() + 5.0)
        assert diag is not None
        w._dump(diag)
        hb.close()
        trace.export()
        merged = trace.merge_traces(str(tmp_path))
        with open(merged) as f:
            events = json.load(f)["traceEvents"]
        stalls = [
            e for e in events if e["ph"] == "i" and e["name"] == "stall"
        ]
        assert len(stalls) == 1
        assert stalls[0]["args"]["component"] == "wedged-component"
        report = analyze_dir(str(tmp_path))
        assert report["stalls"]["trace_markers"] == 1
        assert report["stalls"]["components"] == {"wedged-component": 1}

    def test_dump_without_tracing_still_works(self, tmp_path, capsys):
        w = watchdog_lib.Watchdog(
            stall_after=0.01, dump_path=str(tmp_path / "stacks.txt")
        )
        hb = w.register("wedged")
        hb.beat()
        diag = w.check_once(now=trace.monotonic_s() + 5.0)
        w._dump(diag)  # tracing disabled: instant is a no-op, no crash
        hb.close()
        assert "watchdog_stall" in capsys.readouterr().err


class TestSpanAttribution:
    def test_bench_style_spans_produce_attribution(self, tmp_path):
        """Live in-process rings → compact per-family accounting +
        overlap ratio."""
        trace.configure(str(tmp_path), process_label="bench-eval")
        with trace.span("aot_compile_detect", bucket="64x64"):
            pass
        for _ in range(3):
            with trace.span("detect_dispatch"):
                pass
            with trace.span("detect_fetch"):
                pass
        att = span_attribution(trace.snapshot_events())
        assert att is not None
        assert set(att["by_span_s"]) == {
            "aot_compile_detect", "detect_dispatch", "detect_fetch"
        }
        assert att["decomposition"] is None  # no train loop in a bench
        assert 0.0 <= att["overlap_efficiency"]["eval"] <= 1.0

    def test_disabled_tracing_yields_none(self):
        assert span_attribution(trace.snapshot_events()) is None

    def test_train_vocab_yields_decomposition(self, tmp_path):
        trace.configure(str(tmp_path), process_label="t")
        for _ in range(4):
            with trace.span("data_wait"):
                pass
            with trace.span("step"):
                pass
        att = span_attribution(trace.snapshot_events())
        d = att["decomposition"]
        assert d is not None and abs(sum(d.values()) - 1.0) < 0.02


class TestPeakTable:
    def test_known_kinds_and_fallbacks(self, monkeypatch):
        assert device_peak_tflops("TPU v5 lite") == (197.0, "spec")
        assert device_peak_tflops("TPU v4") == (275.0, "spec")
        # Unknown to the table → no peak, whatever the environment says.
        monkeypatch.setenv("RETINANET_PEAK_TFLOPS", "123.5")
        assert device_peak_tflops("cpu") == (None, None)
        assert device_peak_tflops("weird-npu") == (None, None)
        assert device_peak_tflops(None) == (None, None)

    def test_mfu_is_computed_only_against_a_known_peak(self):
        from batchai_retinanet_horovod_coco_tpu.obs.analyze.report import (
            _mfu_section,
        )

        cost = [{
            "ph": "i", "name": "cost_analysis", "ts": 0, "pid": 1, "tid": 1,
            "args": {"target": "train_step", "flops": 19.7e12, "batch": 8},
        }]
        steps = {"steps_per_s": 5.0}
        known = _mfu_section(cost, steps, "TPU v5 lite")
        assert known["peak_tflops"] == 197.0
        assert known["mfu"] == pytest.approx(0.5)
        unknown = _mfu_section(cost, steps, "cpu")
        assert unknown["achieved_tflops"] == known["achieved_tflops"]
        assert unknown["mfu"] is None


class TestAnalyzeEventsUnits:
    def test_overlap_extremes(self):
        """overlap_efficiency ~1 when fetch barely blocks, ~0 when the
        host spends the whole pipeline blocked in fetch."""
        def mk(name, ts, dur):
            return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                    "pid": 1, "tid": 1}

        # Perfect overlap: 10ms pipeline, 2x 10us fetches.
        good = [mk("detect_dispatch", 0, 100), mk("detect_fetch", 5000, 10),
                mk("detect_dispatch", 5100, 100),
                mk("detect_fetch", 9990, 10)]
        rep = analyze_events(good)
        assert rep["pipeline"]["eval"]["overlap_efficiency"] > 0.99
        # No overlap: fetch occupies the whole wall.
        bad = [mk("detect_dispatch", 0, 10),
               mk("detect_fetch", 10, 9990),
               mk("detect_dispatch", 10000, 10),
               mk("detect_fetch", 10010, 9990)]
        rep = analyze_events(bad)
        assert rep["pipeline"]["eval"]["overlap_efficiency"] < 0.01

    def test_fetch_blocking_verdict_without_train_loop(self):
        """A bench eval/serve trace (no `step` spans) still gets a
        fetch-blocking verdict: the detect-ceiling evidence."""
        def mk(name, ts, dur):
            return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                    "pid": 1, "tid": 1}

        rep = analyze_events(
            [mk("detect_dispatch", 0, 10), mk("detect_fetch", 10, 9990),
             mk("detect_dispatch", 10000, 10),
             mk("detect_fetch", 10010, 9990)]
        )
        top = rep["bottlenecks"][0]
        assert top["name"] == "eval_fetch_blocking"
        # The generic fallback does not duplicate the claimed spans.
        assert not any(
            b["name"] == "span:detect_fetch" for b in rep["bottlenecks"]
        )

    def test_starved_feed_queue_correlation(self):
        def span(name, ts, dur):
            return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                    "pid": 1, "tid": 1}

        def counter(name, ts, v):
            return {"ph": "C", "name": name, "ts": ts, "pid": 1, "tid": 2,
                    "args": {"value": v}}

        events = [
            span("step", 0, 1000),
            counter("device-prefetch.qsize", 500, 0),   # empty before wait
            span("data_wait", 1000, 3000),              # starved: depth 0
            span("step", 4000, 1000),
            counter("device-prefetch.qsize", 5500, 2),  # refilled
            span("data_wait", 6000, 1000),              # depth 2: not starved
            span("step", 7000, 1000),
        ]
        rep = analyze_events(events)
        q = rep["queues"]["device-prefetch.qsize"]
        assert q["starved_data_wait_fraction"] == 0.75  # 3ms of 4ms waits
        assert q["zero_fraction"] == 0.5

    def test_memory_trend(self):
        def counter(name, ts, v):
            return {"ph": "C", "name": name, "ts": ts, "pid": 1, "tid": 1,
                    "args": {"value": v}}

        events = [
            counter("dev0.bytes_in_use", 0, 100.0),
            counter("dev0.bytes_in_use", 1_000_000, 300.0),  # +200B over 1s
            counter("dev0.bytes_in_use", 2_000_000, 200.0),
        ]
        rep = analyze_events(events)
        g = rep["memory"]["gauges"]["dev0.bytes_in_use"]
        assert g["peak_bytes"] == 300.0
        assert g["trend_bytes_per_s"] == 50.0  # (200-100)/2s
        assert rep["memory"]["available"] is True
        # Memory gauges stay out of the queue section.
        assert "dev0.bytes_in_use" not in rep["queues"]
