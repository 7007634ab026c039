"""Measured schedule search + per-device schedule registry (ISSUE 6).

The TVM lesson (PAPERS.md): kernel/batch schedules are SEARCHED per
device, not hand-picked, and winners are versioned artifacts.  This
package owns that loop for the repo's tunable hot-path parameters —
Pallas tile/block shapes (focal, matching, NMS), ``pre_nms_size``, and
per-bucket batch sizes:

- ``schedule``   — the persistent registry: one schema-validated JSON per
  ``device_kind`` under ``artifacts/schedules/``, deep-merged over the
  built-in defaults at lookup; unknown devices fall back to defaults with
  ONE loud structured event, never a crash.  Import-light (no jax).
- ``candidates`` — candidate generation per op family.
- ``search``     — the timed search harness: AOT-compile each candidate,
  two disjoint timed windows, trial spans/events
  through obs, winner composition into a registry artifact.

Consumers look winners up instead of hardcoding: ``train/step.py``
(matching/focal kernel params), ``evaluate/detect.py`` + ``serve/engine.py``
(NMS impl/block, ``pre_nms_size``, per-bucket batch sizes) and
``convert_model.py`` (schedule provenance recorded in the export
manifest).  CLI: ``python -m batchai_retinanet_horovod_coco_tpu.tune``
(``make tune-smoke`` / ``make tune``; RUNBOOK "Autotuning schedules").
"""

from batchai_retinanet_horovod_coco_tpu.tune.schedule import (
    DEFAULT_SCHEDULE,
    ScheduleError,
    load_schedule,
    lookup,
    provenance,
    save_schedule,
    schedule_path,
    serve_batch_sizes_for,
    validate_schedule,
)

__all__ = [
    "DEFAULT_SCHEDULE",
    "ScheduleError",
    "load_schedule",
    "lookup",
    "provenance",
    "save_schedule",
    "schedule_path",
    "serve_batch_sizes_for",
    "validate_schedule",
]
