"""The device as JAX reports it; the benchmark has no CPU mode."""

from __future__ import annotations

import sys


def require_accelerator(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero with the reason.
    Tests replace this function; ``run.py`` has no option that does."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(
            f"benchmark: needs a TPU, JAX found platform {platform!r} "
            f"({len(devices)} devices); there is no fallback"
        )
    if len(devices) < chips:
        sys.exit(
            f"benchmark: the cell needs {chips} chips, JAX found {len(devices)}"
        )
    return devices[:chips]


def device_info(devices) -> dict:
    # The allocator counts buffers (peak_bytes_in_use) and, apart from them,
    # what it reserves for a running program's temporaries
    # (peak_bytes_reserved: 8.75 GB for the flagship train step, which
    # peak_bytes_in_use alone, 1.23 GB, does not show; my chip run, PR 22).
    # A program holds both at once, so the chip's peak is their sum; where
    # the two peaks fell at different moments the sum is an upper bound.
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def memory_stats(device) -> dict:
    """Everything the backend's allocator reports for one device."""
    return {k: int(v) for k, v in (device.memory_stats() or {}).items()}
