"""Per-device schedule registry: schema-validated JSON, loud fallback.

One artifact per ``device_kind`` under ``artifacts/schedules/`` (e.g.
``tpu_v5_lite.json``), written by the search harness (tune/search.py) and
read by every schedule consumer.  The contract:

- **Schema-validated at load** (:func:`validate_schedule`): a committed
  artifact that drifts from the schema fails loudly at ``load_schedule``
  with every problem named — a malformed winner must never silently
  deoptimize (or semantically change) a consumer.
- **Unknown device_kind falls back to today's defaults with ONE loud
  structured event** (:func:`lookup`): a JSON line on stderr naming the
  device and the reason, once per (device, reason) per process — never a
  crash, because an untuned device must still train/serve at the
  hand-picked defaults every consumer shipped with before ISSUE 6.
- **Partial schedules deep-merge over the defaults**: an artifact may
  record only the ops it searched.

This module is import-light (stdlib + obs-free) so jax-free processes —
the shm decode workers transitively import config modules — can always
import consumers that import it.
"""

from __future__ import annotations

import copy
import json
import os
import re
import sys
from typing import Any

FORMAT = "retinanet.schedule.v1"

# Today's hand-picked defaults, exactly as the consumers hardcoded them
# before ISSUE 6 (ops/pallas/{focal,matching,nms}.py constants,
# DetectConfig/serve defaults).  ``impl: "auto"`` preserves a consumer's
# backend-conditional dispatch (matching: fused Pallas on TPU only).
DEFAULT_SCHEDULE: dict[str, Any] = {
    "nms": {"impl": "xla", "block_k": 256, "pre_nms_size": 1000},
    "focal": {"impl": "xla", "fwd_tile_a": 8192, "bwd_tile_a": 4096},
    "matching": {"impl": "auto", "tile_a": 8192},
    # Per-bucket batch sizes ("HxW" -> int for eval/train consumers,
    # "HxW" -> [int, ...] for the serve engine's executable table).
    "eval": {"batch": {}},
    "serve": {"batch_sizes": {}},
}

_IMPLS = {"xla", "pallas", "auto"}
_BUCKET_RE = re.compile(r"^\d+x\d+$")


class ScheduleError(ValueError):
    """A schedule artifact violates the schema (every problem listed)."""


def _check_tile(problems: list[str], op: str, key: str, value: Any) -> None:
    if not isinstance(value, int) or value <= 0 or value % 128 != 0:
        problems.append(
            f"{op}.{key}: must be a positive multiple of 128, got {value!r}"
        )


def validate_schedule(doc: Any) -> dict:
    """Validate a schedule document; returns it, or raises ScheduleError
    naming EVERY problem (not just the first)."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        raise ScheduleError(f"schedule must be a JSON object, got {type(doc).__name__}")
    if doc.get("format") != FORMAT:
        problems.append(
            f"format: expected {FORMAT!r}, got {doc.get('format')!r}"
        )
    kind = doc.get("device_kind")
    if not isinstance(kind, str) or not kind:
        problems.append(f"device_kind: non-empty string required, got {kind!r}")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        problems.append(f"entries: object required, got {type(entries).__name__}")
        entries = {}
    unknown = sorted(set(entries) - set(DEFAULT_SCHEDULE))
    if unknown:
        problems.append(
            f"entries: unknown op keys {unknown} (known: "
            f"{sorted(DEFAULT_SCHEDULE)})"
        )
    for op in ("nms", "focal", "matching"):
        e = entries.get(op)
        if e is None:
            continue
        if not isinstance(e, dict):
            problems.append(f"{op}: object required")
            continue
        bad = sorted(set(e) - set(DEFAULT_SCHEDULE[op]))
        if bad:
            problems.append(f"{op}: unknown keys {bad}")
        impl = e.get("impl")
        if impl is not None and impl not in _IMPLS:
            problems.append(f"{op}.impl: must be one of {sorted(_IMPLS)}, got {impl!r}")
        for key in ("block_k", "fwd_tile_a", "bwd_tile_a", "tile_a"):
            if key in e:
                _check_tile(problems, op, key, e[key])
        if "pre_nms_size" in e:
            v = e["pre_nms_size"]
            if not isinstance(v, int) or not (1 <= v <= 100_000):
                problems.append(
                    f"nms.pre_nms_size: int in [1, 100000] required, got {v!r}"
                )
    for op, key, want_list in (("eval", "batch", False), ("serve", "batch_sizes", True)):
        e = entries.get(op)
        if e is None:
            continue
        if not isinstance(e, dict) or set(e) - {key}:
            problems.append(f"{op}: object with only {key!r} allowed")
            continue
        table = e.get(key, {})
        if not isinstance(table, dict):
            problems.append(f"{op}.{key}: object required")
            continue
        for bucket, v in table.items():
            if not _BUCKET_RE.match(str(bucket)):
                problems.append(f"{op}.{key}: bucket key {bucket!r} is not HxW")
            if want_list:
                ok = (
                    isinstance(v, list) and v
                    and all(isinstance(b, int) and b > 0 for b in v)
                )
                if not ok:
                    problems.append(
                        f"{op}.{key}[{bucket}]: non-empty list of positive "
                        f"ints required, got {v!r}"
                    )
            elif not isinstance(v, int) or v <= 0:
                problems.append(
                    f"{op}.{key}[{bucket}]: positive int required, got {v!r}"
                )
    if "trials" in doc and not isinstance(doc["trials"], list):
        problems.append("trials: list required when present")
    if problems:
        raise ScheduleError(
            "invalid schedule artifact:\n  - " + "\n  - ".join(problems)
        )
    return doc


def device_slug(device_kind: str) -> str:
    """'TPU v5 lite' → 'tpu_v5_lite' (artifact filename stem)."""
    return re.sub(r"[^a-z0-9]+", "_", device_kind.lower()).strip("_") or "unknown"


def schedule_dir(root: str | None = None) -> str:
    """artifacts/schedules/ under the repo root (or ``root``;
    ``RETINANET_SCHEDULE_DIR`` overrides for tests/deployments)."""
    if root is not None:
        return root
    env = os.environ.get("RETINANET_SCHEDULE_DIR")
    if env:
        return env
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(repo, "artifacts", "schedules")


def schedule_path(device_kind: str, root: str | None = None) -> str:
    return os.path.join(schedule_dir(root), f"{device_slug(device_kind)}.json")


def save_schedule(doc: dict, root: str | None = None) -> str:
    """Validate + write one device's schedule artifact; returns the path.
    Atomic: every train/eval/serve/export bring-up resolves this file by
    path — a torn registry must be unobservable."""
    from batchai_retinanet_horovod_coco_tpu.utils.atomicio import (
        atomic_write_text,
    )

    validate_schedule(doc)
    path = schedule_path(doc["device_kind"], root)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    atomic_write_text(
        path, json.dumps(doc, indent=2, sort_keys=True) + "\n"
    )
    _cache_clear()
    return path


def load_schedule(path: str) -> dict:
    """Read + schema-validate one artifact; raises on any violation."""
    with open(path) as f:
        return validate_schedule(json.load(f))


def _merged(entries: dict) -> dict:
    out = copy.deepcopy(DEFAULT_SCHEDULE)
    for op, e in entries.items():
        out[op].update(e)
    return out


def _resolve_device_kind(device_kind: str | None) -> str:
    if device_kind is not None:
        return device_kind
    # Only read jax if something else already imported it — a config
    # lookup must never force a backend init (events.py's discipline).
    jax = sys.modules.get("jax")
    if jax is None:
        return "unknown"
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


# One loud event per (device, reason-class) per process, not per lookup:
# the train loop resolves the schedule once per bucket compile and a
# thousand identical warnings would bury the one that matters.
_warned: set[tuple[str, str]] = set()
_cache: dict[str, tuple[dict, str]] = {}


def _cache_clear() -> None:
    _cache.clear()


def _emit_fallback(device_kind: str, reason: str, detail: str) -> None:
    key = (device_kind, reason)
    if key in _warned:
        return
    _warned.add(key)
    print(
        json.dumps(
            {
                "event": "schedule_fallback",
                "device_kind": device_kind,
                "reason": reason,
                "detail": detail[:500],
                "using": "built-in defaults",
            }
        ),
        file=sys.stderr,
        flush=True,
    )


def lookup(
    device_kind: str | None = None, root: str | None = None
) -> dict[str, Any]:
    """The consumer entrypoint: merged schedule entries for this device.

    Returns ``DEFAULT_SCHEDULE`` deep-merged with the device's committed
    artifact when one exists and validates; otherwise the defaults, with
    one structured ``schedule_fallback`` event on stderr per process
    (missing artifact OR invalid artifact — an implicit lookup must never
    crash a training/serving run; use :func:`load_schedule` for strict
    reads).  Results are cached per device_kind for the process lifetime
    — schedules are immutable once committed, and a stable resolution is
    what guarantees zero request-time recompiles in serve.
    """
    kind = _resolve_device_kind(device_kind)
    path = schedule_path(kind, root)
    # The resolved PATH is the cache key: it folds in root AND the
    # RETINANET_SCHEDULE_DIR env override, so a test (or a redeploy) that
    # repoints the registry dir can never be served another dir's entry.
    cache_key = path
    hit = _cache.get(cache_key)
    if hit is not None:
        return copy.deepcopy(hit[0])
    if not os.path.exists(path):
        _emit_fallback(kind, "no_schedule_artifact", path)
        merged = _merged({})
    else:
        try:
            merged = _merged(load_schedule(path)["entries"])
        except (ScheduleError, OSError, ValueError) as e:
            _emit_fallback(kind, "invalid_schedule_artifact", f"{path}: {e}")
            merged = _merged({})
    _cache[cache_key] = (merged, path)
    return copy.deepcopy(merged)


def serve_batch_sizes_for(
    hw: tuple[int, int],
    default: tuple[int, ...],
    device_kind: str | None = None,
    root: str | None = None,
) -> tuple[int, ...]:
    """Per-bucket serve executable batch sizes (DetectEngine.from_state's
    consumer); ``default`` when the bucket is untuned."""
    table = lookup(device_kind, root)["serve"]["batch_sizes"]
    sizes = table.get(f"{hw[0]}x{hw[1]}")
    return tuple(int(b) for b in sizes) if sizes else tuple(default)


def provenance(
    device_kind: str | None = None, root: str | None = None
) -> dict[str, Any]:
    """Where this device's schedule came from (for manifest records):
    ``{"device_kind", "source" (path or "defaults"), "found"}``."""
    kind = _resolve_device_kind(device_kind)
    path = schedule_path(kind, root)
    found = False
    if os.path.exists(path):
        try:
            load_schedule(path)
            found = True
        except (ScheduleError, OSError, ValueError):
            found = False
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    if found and os.path.abspath(path).startswith(repo + os.sep):
        # Repo-relative in committed records (manifests):
        # an absolute sandbox path says nothing to the next machine.
        path = os.path.relpath(path, repo)
    return {
        "device_kind": kind,
        "source": path if found else "defaults",
        "found": found,
    }
