"""The event vocabulary: every structured-event / trace-instant /
telemetry-series NAME the tree emits, with its intended consumers.

The observability surface is stringly-typed — ``sink.event("repin", ...)``
on the producer side, ``ev["event"] == "repin"`` in a report section or
smoke check on the consumer side — and the PR 16 review round showed what
happens when the two drift (a metric said 2 repins, the parseable event
stream said 0).  This registry is the contract the static
``event-vocabulary`` rule (analysis/rules/event_vocabulary.py) enforces
tree-wide:

- every emit site's name literal must appear here (else
  *emitted-but-unregistered*);
- every entry must still be emitted somewhere (else *stale* or, worse,
  *consumed-but-never-emitted* when a declared consumer still reads it);
- every declared consumer path must be a real scanned file.

The rule parses this module STATICALLY (the dict below must stay a plain
literal — no comprehensions, no computed keys).  Entry shape:

``"name": {"kinds": (...), "consumers": (...)}``

- ``kinds`` — any of ``"event"`` (EventSink.event / emit_event JSONL),
  ``"instant"`` (trace.instant), ``"series"`` (telemetry counter/gauge/
  histogram constructors and trace.counter samples), ``"phase"``
  (trace.phase / trace.record_phase: the set-up record, ISSUE 34).  The
  benchmark's readers of the phases (``benchmark/harness/setup_phases.py``
  and the eight ``benchmark/layer_metrics/setup.*.py``) lie outside the
  scanned tree and cannot be listed: ``setup.step_builds`` and
  ``setup.step_build_s`` read ``compile_train_step``, ``setup.init_state_s``
  ``init_state``, ``setup.place_state_s`` ``place_state``,
  ``setup.trace_lower_s`` ``jit_trace`` and ``jit_lower``, and
  ``setup.cache_load_s``, ``setup.backend_compile_s`` and
  ``setup.cache_misses`` ``xla_compile_or_load``.
- ``consumers`` — repo-relative paths of the files that READ the name
  (report sections, SLO rules, smoke drivers).  Empty means
  "emitted for ad-hoc analysis"; the rule only checks listed paths.

Runtime code may import :data:`VOCABULARY` (stdlib-only, jax-free) but
nothing requires it — the registry is primarily a static contract.
"""

from __future__ import annotations

#: name -> {"kinds": tuple[str, ...], "consumers": tuple[str, ...]}
VOCABULARY: dict[str, dict] = {
    "auto_resume": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
            "train.py",
        ),
    },
    "autoscale_decision": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
        ),
    },
    "autoscale_launch_failed": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "autoscaler_armed": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
        ),
    },
    "backend_init": {
        "kinds": ("phase",),
        "consumers": (),
    },
    "canary_promoted": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "canary_rollback": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
        ),
    },
    "canary_started": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "ckpt_restore": {
        "kinds": ("phase",),
        "consumers": (),
    },
    "ckpt_saved": {
        "kinds": ("event",),
        "consumers": (
            "scripts/chaos.py",
        ),
    },
    "compile_train_step": {
        "kinds": ("phase",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "cost_analysis": {
        "kinds": ("instant",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "ef_reset": {
        "kinds": ("event",),
        "consumers": (
            "scripts/chaos.py",
        ),
    },
    "eval_consumer.qsize": {
        "kinds": ("series",),
        "consumers": (),
    },
    "fleet_breaker_close": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_breaker_half_open": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_breaker_open": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_redispatch": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_replica_died": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_replica_draining": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_replica_joined": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
        ),
    },
    "fleet_replica_removed": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_replica_respawned": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
            "scripts/fleet_obs_smoke.py",
        ),
    },
    "fleet_replica_spawned": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/chaos.py",
            "scripts/fleet_obs_smoke.py",
            "scripts/stream_smoke.py",
        ),
    },
    "fleet_request_latency_ms": {
        "kinds": ("series",),
        "consumers": (
            "scripts/chaos.py",
        ),
    },
    "fleet_respawn_failed": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "fleet_stream_reaped": {
        "kinds": ("event",),
        "consumers": (),
    },
    "init_state": {
        "kinds": ("phase",),
        "consumers": (),
    },
    "jit_lower": {
        "kinds": ("phase",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/utils/backend.py",
        ),
    },
    "jit_trace": {
        "kinds": ("phase",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/utils/backend.py",
        ),
    },
    "numerics_trip": {
        "kinds": ("instant",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/numerics_smoke.py",
        ),
    },
    "perf_report_error": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "train.py",
        ),
    },
    "place_state": {
        "kinds": ("phase",),
        "consumers": (),
    },
    "respawn_budget_exhausted": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "run_meta": {
        "kinds": ("instant",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "serve.admission_qsize": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve.dispatch_qsize": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve.request_latency": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve_batch_occupancy": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve_free_slots": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve_request_latency_ms": {
        "kinds": ("series",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "scripts/telemetry_smoke.py",
        ),
    },
    "serve_slot_wait_ms": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve_stats": {
        "kinds": ("event",),
        "consumers": (),
    },
    "serve_stream_cache_hits_total": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve_stream_cache_misses_total": {
        "kinds": ("series",),
        "consumers": (),
    },
    "serve_stream_frame_latency_ms": {
        "kinds": ("series",),
        "consumers": (),
    },
    "shm.inflight_batches": {
        "kinds": ("series",),
        "consumers": (),
    },
    "shm.out_qsize": {
        "kinds": ("series",),
        "consumers": (),
    },
    "slo_violation": {
        "kinds": ("event", "instant"),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
            "batchai_retinanet_horovod_coco_tpu/obs/slo.py",
            "scripts/fleet_obs_smoke.py",
            "scripts/numerics_smoke.py",
        ),
    },
    "stall": {
        "kinds": ("instant",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "stream_opened": {
        "kinds": ("instant",),
        "consumers": (),
    },
    "stream_repinned": {
        "kinds": ("event",),
        "consumers": (
            "scripts/stream_smoke.py",
        ),
    },
    "stream_session_reaped": {
        "kinds": ("instant",),
        "consumers": (),
    },
    "train_comm_compressed_bytes_total": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_comm_dcn_bytes_total": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_comm_ici_bytes_total": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_compiles_total": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_data_wait_fraction": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_data_wait_ms": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_ef_residual": {
        "kinds": ("series",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/slo.py",
        ),
    },
    "train_ef_residual_dcn": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_ef_saturation": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_grad_norm": {
        "kinds": ("series",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/slo.py",
        ),
    },
    "train_images_per_sec": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_last_compile_s": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_nonfinite_total": {
        "kinds": ("series",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/slo.py",
        ),
    },
    "train_replica_agreement": {
        "kinds": ("series",),
        "consumers": (),
    },
    "train_step": {
        "kinds": ("series",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "train_step_time_ms": {
        "kinds": ("series",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/slo.py",
        ),
    },
    "train_update_ratio": {
        "kinds": ("series",),
        "consumers": (),
    },
    "watchdog_stall": {
        "kinds": ("event",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/obs/analyze/report.py",
        ),
    },
    "xla_compile_or_load": {
        "kinds": ("phase",),
        "consumers": (
            "batchai_retinanet_horovod_coco_tpu/utils/backend.py",
        ),
    },
}


def names() -> tuple[str, ...]:
    """Every registered name (sorted) — for runtime validation hooks."""
    return tuple(sorted(VOCABULARY))
