"""On-demand g++ compilation + ctypes loading of the native kernels.

No pybind11 in this environment (and no Python.h dependency wanted): the
kernels expose a plain C ABI and are bound with ctypes.  The .so is rebuilt
whenever the source is newer (mtime) and cached next to the source; if no
toolchain is available the caller falls back to its pure-numpy path, so the
framework never hard-requires a compiler at runtime.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_CACHE: dict[str, ctypes.CDLL | None] = {}
# How each loaded library came to be, for callers that must say so
# (chip_smoke.py): "built" from the .cpp by this process, or "reused"
# from a .so an earlier process in this checkout built.
_ORIGIN: dict[str, str] = {}


def _compile(src: str, lib: str, extra_flags: tuple[str, ...] = ()) -> bool:
    tmp_path = None
    try:
        with tempfile.NamedTemporaryFile(
            suffix=".so", dir=_DIR, delete=False
        ) as tmp:
            tmp_path = tmp.name
        # No -march=native: a cached .so may travel to another host (rsync,
        # docker COPY preserve mtimes) where exotic ISA extensions would
        # SIGILL with no way to fall back.  -ffp-contract=off keeps bit
        # parity with the numpy oracle (no FMA contraction).
        cmd = [
            "g++", "-O3", "-shared", "-fPIC", "-ffp-contract=off",
            *extra_flags, "-o", tmp_path, src,
        ]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, lib)  # atomic under concurrent builders
        return True
    except (OSError, subprocess.SubprocessError):
        if tmp_path is not None:
            try:
                os.unlink(tmp_path)
            except OSError:
                pass
        return False


_ASAN_FLAGS = ("-fsanitize=address", "-g", "-fno-omit-frame-pointer")


def load_library(name: str = "cocoeval", sanitize: bool = False) -> ctypes.CDLL | None:
    """Load (building if stale) ``native/<name>.cpp`` → CDLL, or None.

    ``sanitize=True`` builds an AddressSanitizer variant
    (``lib<name>_asan.so``) — the §5.2 sanitizer target for the native
    kernels (SURVEY.md).  Loading it requires libasan in the process
    (LD_PRELOAD for a stock Python); tests/unit/test_native_asan.py runs
    the kernels under it in a subprocess.
    """
    key = f"{name}+asan" if sanitize else name
    with _LOCK:
        if key in _CACHE:
            return _CACHE[key]
        src = os.path.join(_DIR, f"{name}.cpp")
        suffix = "_asan" if sanitize else ""
        lib = os.path.join(_DIR, f"lib{name}{suffix}.so")
        result: ctypes.CDLL | None = None
        if os.path.exists(src):
            # Strict >: a fresh checkout gives .so and .cpp equal mtimes, and
            # a checked-out binary (wrong ISA, stale) must be rebuilt.
            fresh = os.path.exists(lib) and os.path.getmtime(
                lib
            ) > os.path.getmtime(src)
            flags = _ASAN_FLAGS if sanitize else ()
            if fresh or _compile(src, lib, flags):
                try:
                    result = ctypes.CDLL(lib)
                    _ORIGIN[key] = "reused" if fresh else "built"
                except OSError:
                    result = None
        _CACHE[key] = result
        return result


def library_origin(name: str = "cocoeval") -> str | None:
    """"built" | "reused" for a library :func:`load_library` has loaded,
    None when it has not been loaded (or could not be)."""
    return _ORIGIN.get(name)
