"""Perf doctor (ISSUE 8): the obs subsystem's read side.

``report`` turns a run's own artifacts (merged ``trace.json``, events
JSONL via ``split_runs``, watchdog markers) into one machine-readable
``PERF_REPORT.json`` — step-time decomposition, pipeline overlap
efficiency, queue/stall correlation, memory trend, an MFU estimate from
the recorded XLA cost-analysis FLOPs, and a ranked top-3 bottleneck
verdict naming the spans to attack next.

Three entrypoints:

- inline auto-emit at ``train.py``'s finalize (``auto_emit`` — never
  raises; failure is one structured event);
- offline CLI: ``python -m batchai_retinanet_horovod_coco_tpu.obs.analyze
  <obs_dir>`` (byte-identical to the inline report for the same dir);
- ``make perf-report`` (the CLI over ``OBS_DIR``).

jax-free: the analyzer reads artifacts, never devices.
"""

from batchai_retinanet_horovod_coco_tpu.obs.analyze.report import (
    AnalyzeError,
    PEAK_TFLOPS,
    SCHEMA_VERSION,
    analyze_dir,
    analyze_events,
    auto_emit,
    device_peak_tflops,
    load_trace,
    span_attribution,
    validate_report,
    write_report,
)

__all__ = [
    "AnalyzeError",
    "PEAK_TFLOPS",
    "SCHEMA_VERSION",
    "analyze_dir",
    "analyze_events",
    "auto_emit",
    "device_peak_tflops",
    "load_trace",
    "span_attribution",
    "validate_report",
    "write_report",
]
