"""Process set-up shared by every entry point that touches the device.

Two things, both called once at the top of an entry point, after its
``--platform`` handling and before it builds anything:

- :func:`enable_compile_cache` gives the process a persistent XLA compile
  cache at a place that can be chosen from OUTSIDE.  A cold flagship run
  compiles for minutes (train step, detect program per bucket, serve
  executables); without a cache every process pays all of it again.
- :func:`announce_devices` prints the one line that says which backend the
  run actually got — ``--platform auto`` takes whatever JAX finds, and a
  run that silently landed on the CPU must be visible in its first line
  of output, not inferred from its speed.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — derived from the package's location, so every
# process started from this checkout (and every later run in it) resolves
# the same directory.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache(default_dir: str = DEFAULT_CACHE_DIR) -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    A cache that is already placed stays where it is: with
    ``JAX_COMPILATION_CACHE_DIR`` set JAX has read it itself, and a
    process that runs several entry points in turn (chip_smoke.py, the
    tests) keeps the first placement.  Otherwise → ``default_dir``, a
    FIXED path: never a temp dir, pid or timestamp, so a second run finds
    what the first compiled.
    """
    import jax

    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir


def announce_devices(entry: str) -> None:
    """Print ``<entry>: platform=… device_kind=… devices=N`` — the device
    as JAX reports it (this initializes the backend).  Call before
    building models, pipelines or engines."""
    import jax

    devices = jax.devices()
    print(
        f"{entry}: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} devices={len(devices)}",
        flush=True,
    )
