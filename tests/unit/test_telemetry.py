"""Live telemetry plane tests (ISSUE 9, obs/telemetry.py + obs/slo.py).

The satellite checklist, pinned:

- registry concurrency (parallel inc/observe lose nothing),
- exposition-format golden (byte-for-byte Prometheus text) + the
  parse_exposition round-trip,
- /healthz flips 503 naming the component on an injected watchdog stall
  (unit probe AND through the serve HTTP frontend),
- the train status server starts, serves, and drains cleanly
  (bounded, idempotent close; socket actually released),
- an SLO rule fires EXACTLY ONCE per sustained breach (no flapping),
  re-arms only after clear_s of health, regression + delta modes,
- disabled-path overhead: record sites are one bool check — structurally
  a no-op (no state mutated) while telemetry is off,
- obs/analyze ingests slo_violation events: violations section + the
  slo:* verdict ranked above inferred bottlenecks.

Stub-engine serve tests only (no jax compile in the loop) — the real
end-to-end scrape runs in scripts/telemetry_smoke.py (make
telemetry-smoke).
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from batchai_retinanet_horovod_coco_tpu.obs import slo, telemetry, trace, watchdog
from batchai_retinanet_horovod_coco_tpu.obs.telemetry import (
    Registry,
    StatusServer,
    healthz,
    parse_exposition,
)


@pytest.fixture(autouse=True)
def _telemetry_state():
    """Every test starts and ends with the push gate off and a fresh
    default registry (module-global state, like the trace tests)."""
    telemetry.reset()
    trace.reset()
    yield
    telemetry.reset()
    trace.reset()


def _get(url: str) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ---- registry ------------------------------------------------------------


class TestRegistry:
    def test_concurrent_increments_lose_nothing(self):
        telemetry.enable()
        reg = Registry()
        c = reg.counter("requests_total")
        h = reg.histogram("latency_ms", window=100_000)
        n_threads, per_thread = 8, 2000
        errors: list[BaseException] = []

        def work():
            try:
                for _ in range(per_thread):
                    c.inc()
                    c.inc(reason="shed")
                    h.observe(1.0)
            except BaseException as e:  # surfaced after the join
                errors.append(e)

        # watchdog: short-lived test workers, joined 4 lines below.
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        snap = reg.snapshot()
        assert snap["requests_total"] == n_threads * per_thread
        assert snap['requests_total{reason="shed"}'] == n_threads * per_thread
        assert snap["latency_ms.count"] == n_threads * per_thread

    def test_type_conflict_and_bad_names_raise(self):
        reg = Registry()
        reg.counter("a_total")
        with pytest.raises(ValueError):
            reg.gauge("a_total")
        with pytest.raises(ValueError):
            reg.counter("bad name")
        telemetry.enable()
        with pytest.raises(ValueError):
            reg.counter("ok_total").inc(**{"bad-label": "x"})

    def test_gauge_callback_pull_and_snapshot_aggregates(self):
        telemetry.enable()
        reg = Registry()
        reg.gauge("depth", fn=lambda: 7)
        g = reg.gauge("labeled")
        g.set(3, queue="a")
        g.set(5, queue="b")
        c = reg.counter("shed_total")
        c.inc(2, reason="x")
        c.inc(3, reason="y")
        snap = reg.snapshot()
        assert snap["depth"] == 7
        assert snap["labeled"] == 5  # gauges aggregate with max
        assert snap["shed_total"] == 5  # counters aggregate with sum

    def test_collector_callback_and_dead_collector_skipped(self):
        reg = Registry()
        reg.register_collector(
            lambda: [("x_total", "counter", "", None, 4.0)]
        )

        def dead():
            raise RuntimeError("boom")

        reg.register_collector(dead)
        assert reg.snapshot()["x_total"] == 4.0  # scrape survives


class TestDisabledOverhead:
    def test_record_sites_are_noops_while_disabled(self):
        """The acceptance bar: with telemetry off, a record site is one
        bool check — structurally, NO state may change (the timing twin
        of PR 3's shared-noop span test)."""
        assert not telemetry.enabled()
        reg = Registry()
        c = reg.counter("c_total")
        g = reg.gauge("g")
        h = reg.histogram("h_ms")
        c.inc()
        g.set(5)
        h.observe(1.0)
        assert c.samples() == []
        assert g.samples() == []
        assert h.window_ms() == []
        telemetry.record_train_window(
            step=1, images_per_s=1, step_time_ms=1, data_wait_ms=1
        )
        telemetry.record_compile("64x64", 1.0)
        # The disabled-path record sites must not even have built the
        # train metric handles on the default registry.
        assert telemetry._train_gauges is None

    def test_record_sites_feed_default_registry_when_enabled(self):
        telemetry.enable()
        telemetry.record_train_window(
            step=7, images_per_s=12.5, step_time_ms=80.0, data_wait_ms=20.0
        )
        telemetry.record_compile("64x64", 2.5)
        snap = telemetry.default().snapshot()
        assert snap["train_step"] == 7
        assert snap["train_images_per_sec"] == 12.5
        assert snap["train_data_wait_fraction"] == 0.25
        assert snap['train_compiles_total{bucket="64x64"}'] == 1
        assert snap["train_last_compile_s"] == 2.5
        # Built-in collectors ride along on the default registry.
        assert "process_uptime_seconds" in snap
        assert "watchdog_stalled" in snap


# ---- exposition ----------------------------------------------------------


EXPECTED_EXPOSITION = """\
# HELP q_depth live queue depths
# TYPE q_depth gauge
q_depth{queue="admission"} 3
q_depth{queue="bucket_64x64"} 0
# HELP req_latency_ms request latency
# TYPE req_latency_ms summary
req_latency_ms{quantile="0.5"} 2
req_latency_ms{quantile="0.9"} 80.4
req_latency_ms{quantile="0.99"} 98.04
req_latency_ms_count 3
req_latency_ms_sum 103
# HELP shed_total sheds by reason
# TYPE shed_total counter
shed_total{reason="admission_queue_full"} 2
shed_total{reason="with\\"quote"} 1
"""


def _golden_registry() -> Registry:
    reg = Registry()
    c = reg.counter("shed_total", "sheds by reason")
    c.inc(2, reason="admission_queue_full")
    c.inc(reason='with"quote')
    g = reg.gauge("q_depth", "live queue depths")
    g.set(3, queue="admission")
    g.set(0, queue="bucket_64x64")
    reg.histogram(
        "req_latency_ms", "request latency",
        source=lambda: [1.0, 2.0, 100.0],
    )
    return reg


class TestExposition:
    def test_prometheus_text_golden(self):
        telemetry.enable()
        assert _golden_registry().prometheus_text() == EXPECTED_EXPOSITION

    def test_parse_round_trip(self):
        telemetry.enable()
        reg = _golden_registry()
        types, samples = parse_exposition(reg.prometheus_text())
        assert types == {
            "shed_total": "counter",
            "q_depth": "gauge",
            "req_latency_ms": "summary",
        }
        assert samples['shed_total{reason="admission_queue_full"}'] == 2
        assert samples['q_depth{queue="admission"}'] == 3
        assert samples['req_latency_ms{quantile="0.99"}'] == 98.04
        assert samples["req_latency_ms_count"] == 3
        # parse agrees with snapshot through the other path
        snap = reg.snapshot()
        assert snap["req_latency_ms.p99"] == 98.04
        assert snap["shed_total"] == 3


# ---- healthz -------------------------------------------------------------


class TestHealthz:
    def test_flips_503_on_injected_stall_and_recovers(self):
        wd = watchdog.Watchdog(stall_after=100.0)
        code, payload = healthz(wd)
        assert code == 200 and payload["status"] == "ok"
        hb = wd.register("wedged-component", stall_after=0.01)
        hb2 = wd.register("healthy-component")
        time.sleep(0.05)
        hb2.beat()
        code, payload = healthz(wd)
        assert code == 503
        assert payload["component"] == "wedged-component"
        assert payload["stalled"][0]["stalled_for_s"] > 0.01
        assert "healthy-component" in payload["components"]
        hb.beat()  # recovery
        code, payload = healthz(wd)
        assert code == 200
        hb.close()
        hb2.close()

    def test_idle_components_never_flag(self):
        wd = watchdog.Watchdog()
        hb = wd.register("quiescent", stall_after=0.01)
        hb.idle()
        time.sleep(0.03)
        code, _payload = healthz(wd)
        assert code == 200
        hb.close()

    def test_probe_is_read_only(self):
        """stalled_components must not eat the poll thread's
        one-dump-per-stall latch."""
        wd = watchdog.Watchdog(stall_after=0.01)
        hb = wd.register("wedged")
        time.sleep(0.03)
        assert wd.stalled_components()  # the healthz probe...
        diag = wd.check_once()  # ...must not have consumed the dump
        assert diag is not None and diag["component"] == "wedged"
        hb.close()


# ---- status server (train.py --obs-port) ---------------------------------


class TestStatusServer:
    def test_serves_and_drains_cleanly(self):
        telemetry.enable()
        reg = Registry()
        reg.counter("x_total").inc(3)
        server = StatusServer(reg, port=0).start()
        base = f"http://{server.host}:{server.port}"
        code, body = _get(f"{base}/metrics")
        assert code == 200 and b"x_total 3" in body
        code, body = _get(f"{base}/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        code, body = _get(f"{base}/statusz")
        assert code == 200 and json.loads(body)["x_total"] == 3
        code, _body = _get(f"{base}/nope")
        assert code == 404
        # The listener is watchdog-registered while serving...
        assert any(
            n.startswith("obs-telemetry-http")
            for n in watchdog.default().components()
        )
        server.close()
        server.close()  # idempotent
        # ...unregistered after drain, and the socket is released.
        assert not any(
            n.startswith("obs-telemetry-http")
            for n in watchdog.default().components()
        )
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(f"{base}/healthz", timeout=2)

    def test_ephemeral_ports_do_not_collide(self):
        a = StatusServer(Registry(), port=0).start()
        b = StatusServer(Registry(), port=0).start()
        try:
            assert a.port != b.port
        finally:
            a.close()
            b.close()


# ---- SLO monitor ---------------------------------------------------------


class _SinkStub:
    def __init__(self):
        self.events: list[tuple[str, dict]] = []

    def event(self, kind, **fields):
        self.events.append((kind, fields))


class TestSlo:
    def _monitor(self, value_fn, rule, sink=None):
        reg = Registry()
        reg.gauge("m", fn=value_fn)
        return slo.SloMonitor(reg, [rule], sink=sink)

    def test_fires_exactly_once_per_sustained_breach(self):
        """The anti-flap pin: one event per sustained breach, re-armed
        only by clear_s of continuous health."""
        value = [100.0]
        sink = _SinkStub()
        mon = self._monitor(
            lambda: value[0],
            slo.SloRule(
                name="ceiling", metric="m", op=">", threshold=50,
                for_s=2.0, clear_s=3.0,
            ),
            sink=sink,
        )
        t = 1000.0
        assert mon.check_once(now=t) == []  # breached, not yet sustained
        assert mon.check_once(now=t + 1) == []
        fired = mon.check_once(now=t + 2.5)
        assert [v["rule"] for v in fired] == ["ceiling"]
        assert fired[0]["sustained_s"] == 2.5
        # Still breached for hours: the latch holds — NO flapping.
        for dt in (3, 10, 100, 1000):
            assert mon.check_once(now=t + dt) == []
        # Brief health below clear_s does not re-arm...
        value[0] = 1.0
        assert mon.check_once(now=t + 2000) == []
        value[0] = 100.0
        assert mon.check_once(now=t + 2001) == []  # breach_since resets
        assert mon.check_once(now=t + 2004) == []  # latch still held
        # ...but clear_s of continuous health does.
        value[0] = 1.0
        assert mon.check_once(now=t + 3000) == []
        assert mon.check_once(now=t + 3004) == []  # re-armed here
        value[0] = 100.0
        assert mon.check_once(now=t + 3005) == []
        fired = mon.check_once(now=t + 3007.5)
        assert len(fired) == 1
        assert len(sink.events) == 2  # exactly one event per breach
        assert all(k == "slo_violation" for k, _ in sink.events)
        assert mon.registry.snapshot()[
            'slo_violations_total{rule="ceiling"}'
        ] == 2

    def test_violation_reaches_sink_and_trace(self, tmp_path):
        trace.configure(str(tmp_path), process_label="test")
        sink = _SinkStub()
        mon = self._monitor(
            lambda: 9.0,
            slo.SloRule(name="r", metric="m", op=">", threshold=1.0),
            sink=sink,
        )
        assert len(mon.check_once(now=1.0)) == 1
        kind, fields = sink.events[0]
        assert kind == "slo_violation" and fields["rule"] == "r"
        instants = [
            e for e in trace.snapshot_events()
            if e.get("ph") == "i" and e.get("name") == "slo_violation"
        ]
        assert len(instants) == 1
        assert instants[0]["args"]["rule"] == "r"

    def test_missing_metric_is_not_a_breach(self):
        mon = slo.SloMonitor(
            Registry(),
            [slo.SloRule(name="r", metric="absent", op=">", threshold=0)],
        )
        assert mon.check_once(now=1.0) == []
        assert mon.check_once(now=100.0) == []

    def test_delta_rule_measures_per_poll_increase(self):
        value = [0.0]
        mon = self._monitor(
            lambda: value[0],
            slo.SloRule(
                name="shed-rate", metric="m", op=">", threshold=5,
                delta=True, clear_s=0.0,
            ),
        )
        assert mon.check_once(now=1.0) == []  # first sample: no delta yet
        value[0] = 3.0
        assert mon.check_once(now=2.0) == []  # +3 <= 5
        value[0] = 20.0
        assert len(mon.check_once(now=3.0)) == 1  # +17 > 5

    def test_regression_rule_vs_rolling_baseline(self):
        value = [100.0]
        mon = self._monitor(
            lambda: value[0],
            slo.SloRule(
                name="step-regress", metric="m", op=">",
                baseline_window=8, factor=1.5, min_baseline=3,
            ),
        )
        for i in range(5):  # build the healthy baseline
            assert mon.check_once(now=float(i)) == []
        value[0] = 300.0  # 3x the median → breach
        fired = mon.check_once(now=10.0)
        assert len(fired) == 1
        assert fired[0]["threshold"] == pytest.approx(150.0)
        # The breaching samples never poisoned their own baseline.
        assert mon.check_once(now=11.0) == []
        state = mon._states["step-regress"]
        assert max(state.baseline) == 100.0

    def test_stall_rule_and_watchdog_collector(self):
        wd = watchdog.Watchdog()
        reg = Registry()
        reg.register_collector(telemetry.watchdog_collector(wd))
        mon = slo.SloMonitor(reg, [slo.stall_rule()])
        hb = wd.register("wedge", stall_after=0.01)
        assert mon.check_once(now=1.0) == []  # not stalled yet
        time.sleep(0.03)
        fired = mon.check_once(now=2.0)
        assert [v["rule"] for v in fired] == ["watchdog-stall"]
        hb.close()

    def test_poll_thread_starts_and_stops(self):
        mon = self._monitor(
            lambda: 1.0,
            slo.SloRule(name="r", metric="m", op=">", threshold=100),
        )
        mon.poll_interval = 0.01
        mon.start()
        assert "slo-monitor" in watchdog.default().components()
        time.sleep(0.05)
        mon.stop()
        assert "slo-monitor" not in watchdog.default().components()

    def test_parse_rule_grammar(self):
        r = slo.parse_rule("serve_request_latency_ms.p99>250@30")
        assert (r.metric, r.op, r.threshold, r.for_s) == (
            "serve_request_latency_ms.p99", ">", 250.0, 30.0,
        )
        r = slo.parse_rule("train_step_time_ms>x1.5@60")
        assert r.baseline_window > 0 and r.factor == 1.5 and r.for_s == 60.0
        r = slo.parse_rule("train_data_wait_fraction>=0.5")
        assert r.op == ">=" and r.for_s == 0.0
        with pytest.raises(ValueError):
            slo.parse_rule("not a rule")
        with pytest.raises(ValueError):
            slo.SloMonitor(Registry(), [slo.stall_rule(), slo.stall_rule()])


# ---- serve frontend integration (stub engine; no jax compile) ------------


class _Det:
    def __init__(self, boxes, scores, labels, valid):
        self.boxes, self.scores, self.labels = boxes, scores, labels
        self.valid = valid


class StubEngine:
    from batchai_retinanet_horovod_coco_tpu.serve.engine import (
        IdentityLabelMap as _Ident,
    )

    min_side = 64
    max_side = 64
    buckets = ((64, 64),)
    label_to_cat_id = _Ident()

    def batch_sizes(self, hw):
        return [4]

    def max_batch(self, hw):
        return 4

    def batch_size_for(self, hw, n):
        return 4

    def warmup(self):
        pass

    def dispatch(self, hw, images):
        b = images.shape[0]
        boxes = np.tile(
            np.array([[[1.0, 2.0, 10.0, 20.0]]], np.float32), (b, 1, 1)
        )
        return _Det(
            boxes,
            np.full((b, 1), 0.5, np.float32),
            np.zeros((b, 1), np.int32),
            np.ones((b, 1), bool),
        )

    def fetch(self, det):
        return det


IMG = np.zeros((64, 64, 3), np.uint8)


class TestServeTelemetry:
    def _server(self):
        from batchai_retinanet_horovod_coco_tpu.serve import (
            DetectionServer,
            ServeConfig,
        )

        return DetectionServer(
            StubEngine(),
            ServeConfig(max_delay_ms=5.0, preprocess_workers=1),
        )

    def test_metrics_track_snapshot(self):
        with self._server() as srv:
            for _ in range(4):
                srv.submit(IMG).result(timeout=10)
            srv.stats.record_shed("test_injected")
            types, samples = parse_exposition(
                srv.telemetry.prometheus_text()
            )
            snap = srv.snapshot()
            assert types["serve_request_latency_ms"] == "summary"
            assert (
                samples["serve_requests_completed_total"]
                == snap["completed"] == 4
            )
            assert samples['serve_shed_total{reason="test_injected"}'] == 1
            assert samples['serve_queue_depth{queue="admission"}'] == 0
            assert (
                samples['serve_request_latency_ms{quantile="0.99"}']
                == snap["p99_ms"]
            )
            assert samples["serve_queue_capacity{queue=\"admission\"}"] == 128

    def test_http_metrics_healthz_and_stall_flip(self):
        from batchai_retinanet_horovod_coco_tpu.serve import serve_http

        with self._server() as srv:
            srv.submit(IMG).result(timeout=10)
            httpd = serve_http(srv, port=0)
            # watchdog: scrape-lifetime stdlib server, joined below.
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
            try:
                host, port = httpd.server_address[:2]
                base = f"http://{host}:{port}"
                code, body = _get(f"{base}/metrics")
                assert code == 200
                assert b"serve_request_latency_ms" in body
                code, body = _get(f"{base}/healthz")
                payload = json.loads(body)
                assert code == 200 and payload["status"] == "ok"
                load = payload["load"]
                assert load["completed"] == 1 and load["accepting"]
                assert "admission_capacity" in load
                # /healthz is split from /stats: distinct payload shapes.
                code, body = _get(f"{base}/stats")
                assert code == 200 and "status" not in json.loads(body)
                hb = watchdog.register("http-wedge", stall_after=0.01)
                time.sleep(0.05)
                code, body = _get(f"{base}/healthz")
                assert code == 503
                assert json.loads(body)["component"] == "http-wedge"
                hb.close()
            finally:
                httpd.shutdown()
                httpd.server_close()
                t.join(timeout=10)


# ---- obs/analyze ingestion ----------------------------------------------


class TestAnalyzeViolations:
    def _events_file(self, tmp_path) -> str:
        path = tmp_path / "metrics.jsonl"
        records = [
            {"event": "run_header", "run_id": "abc12345", "t_wall": 0.0},
            {
                "event": "slo_violation", "wall_s": 5.0, "rule": "p99",
                "metric": "serve_request_latency_ms.p99", "op": ">",
                "value": 300.0, "threshold": 250.0, "sustained_s": 30.0,
                "description": "p99 ceiling",
            },
        ]
        path.write_text("".join(json.dumps(r) + "\n" for r in records))
        return str(path)

    def test_violations_section_and_verdict_ranking(self, tmp_path):
        from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
            analyze_events,
            validate_report,
        )

        # A trace with one busy span family (an inferable bottleneck)
        # plus the violation's instant marker.
        events = [
            {"ph": "X", "name": "serve_fetch", "ts": 0, "dur": 900_000,
             "pid": 1, "tid": 1},
            {"ph": "i", "name": "slo_violation", "ts": 100, "pid": 1,
             "tid": 1,
             "args": {"rule": "p99",
                      "metric": "serve_request_latency_ms.p99",
                      "value": 300.0, "threshold": 250.0,
                      "sustained_s": 30.0}},
        ]
        report = analyze_events(
            events, events_path=self._events_file(tmp_path)
        )
        assert validate_report(report) == []
        v = report["violations"]
        assert v["jsonl_events"] == 1 and v["trace_markers"] == 1
        assert v["rules"]["p99"]["count"] == 1
        assert v["rules"]["p99"]["max_sustained_s"] == 30.0
        # The sustained violation outranks every inferred bottleneck.
        top = report["bottlenecks"][0]
        assert top["name"] == "slo:p99" and top["rank"] == 1
        assert top["score"] == 1.0
        names = [b["name"] for b in report["bottlenecks"]]
        assert any(n.startswith("span:") for n in names)  # not starved

    def test_no_violations_is_empty_not_missing(self):
        from batchai_retinanet_horovod_coco_tpu.obs.analyze import (
            analyze_events,
        )

        report = analyze_events([])
        assert report["violations"] == {
            "trace_markers": 0, "jsonl_events": 0, "rules": {},
        }


class TestCkptTelemetry:
    """ISSUE 11 satellite: checkpoint health on the telemetry plane —
    the record sites, the pull collector's age arithmetic, and the
    built-in staleness SLO rule that makes a silently wedged saver
    visible before the run dies."""

    def setup_method(self):
        telemetry.reset()
        telemetry.enable()

    def teardown_method(self):
        telemetry.reset()

    def test_record_sites_feed_the_collector(self):
        telemetry.record_ckpt_inflight(1)
        telemetry.record_ckpt_save(step=2, save_s=0.12, total_bytes=1000)
        telemetry.record_ckpt_save(step=4, save_s=0.34, total_bytes=1000)
        snap = telemetry.default().snapshot()
        assert snap["ckpt_saves_total"] == 2
        assert snap["ckpt_save_s"] == pytest.approx(0.34)
        assert snap["ckpt_bytes"] == 1000
        assert snap["ckpt_last_success_age_s"] >= 0
        # Two saves landed -> a measured interval -> the ratio exists.
        assert "ckpt_age_over_interval" in snap
        assert snap["ckpt_inflight"] == 1
        telemetry.record_ckpt_inflight(0)
        assert telemetry.default().snapshot()["ckpt_inflight"] == 0

    def test_no_checkpointing_no_metric_noise(self):
        snap = telemetry.default().snapshot()
        assert not any(k.startswith("ckpt_") for k in snap)

    def test_disabled_record_sites_are_noops(self):
        telemetry.disable()
        telemetry.record_ckpt_save(step=2, save_s=0.1, total_bytes=10)
        telemetry.record_ckpt_inflight(1)
        telemetry.enable()
        snap = telemetry.default().snapshot()
        assert not any(k.startswith("ckpt_") for k in snap)

    def test_staleness_rule_fires_once_when_saver_wedges(self):
        # Saves landed at steps 2 and 4 (measured cadence: 2 steps).
        telemetry.record_ckpt_save(step=2, save_s=0.1, total_bytes=10)
        telemetry.record_ckpt_save(step=4, save_s=0.1, total_bytes=10)
        mon = slo.SloMonitor(
            telemetry.default(), [slo.ckpt_staleness_rule()]
        )
        # Healthy: training at step 5, one step past the save -> 0.5.
        telemetry.record_train_window(
            step=5, images_per_s=1.0, step_time_ms=1.0, data_wait_ms=0.0
        )
        snap = telemetry.default().snapshot()
        assert snap["ckpt_staleness"] == pytest.approx(0.5)
        assert mon.check_once(now=1.0) == []
        # Wedged saver: training advanced 10 steps (5x the cadence) with
        # no save landing.  STEP-based, so a long eval (steps frozen)
        # could never have tripped this.
        telemetry.record_train_window(
            step=14, images_per_s=1.0, step_time_ms=1.0, data_wait_ms=0.0
        )
        fired = mon.check_once(now=2.0)
        assert [v["rule"] for v in fired] == ["ckpt-staleness"]
        assert mon.check_once(now=3.0) == []  # latched, no flapping

    def test_manager_save_lands_on_the_plane(self, tmp_path):
        import jax
        import jax.numpy as jnp
        import optax

        from batchai_retinanet_horovod_coco_tpu.train.state import TrainState
        from batchai_retinanet_horovod_coco_tpu.utils.checkpoint import (
            CheckpointManager,
        )

        state = TrainState(
            step=jnp.asarray(1, jnp.int32),
            params={"w": jnp.ones((4,), jnp.float32)},
            batch_stats={},
            opt_state=(),
            tx=optax.sgd(1e-2),
        )
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(state, step=1, force=True)
        mgr.wait()
        mgr.close()
        snap = telemetry.default().snapshot()
        assert snap["ckpt_saves_total"] == 1
        assert snap["ckpt_inflight"] == 0
        assert snap["ckpt_save_s"] >= 0
