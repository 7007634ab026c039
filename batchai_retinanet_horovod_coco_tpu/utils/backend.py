"""Process set-up shared by every entry point that touches the device.

Two things, both called once at the top of an entry point, after its
``--platform`` handling and before it builds anything:

- :func:`enable_compile_cache` gives the process a persistent XLA compile
  cache at a place that can be chosen from OUTSIDE.  A cold flagship run
  compiles for minutes (train step, detect program per bucket, serve
  executables); without a cache every process pays all of it again.
  It also makes JAX's own trace, lowering and compile events phases of
  ``obs/trace.py`` (ISSUE 34), so every program a process builds before
  its steady state is on the set-up record with its seconds, the phase it
  was built under and whether the cache held it; :func:`compile_stats`
  sums them.
- :func:`announce_devices` prints the one line that says which backend the
  run actually got — ``--platform auto`` takes whatever JAX finds, and a
  run that silently landed on the CPU must be visible in its first line
  of output, not inferred from its speed.
"""

from __future__ import annotations

import os
import threading

from batchai_retinanet_horovod_coco_tpu.obs import trace

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

    _install_compile_listeners()
    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir


# ---- JAX's compile events as phases ---------------------------------------

# jax 0.9.0: _src/dispatch.py:60-62 (each a start scalar, then a duration and
# a time span when it ends), _src/compiler.py:435-453 and
# _src/compilation_cache.py:283 (fired on the compiling thread inside its
# backend_compile_duration).
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITTEN = "/jax/compilation_cache/cache_misses"  # fired when the new executable is stored
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_listeners_installed = False
_tls = threading.local()


def _asked() -> dict:
    """What the cache has said on this thread since its last compile."""
    asked = getattr(_tls, "asked", None)
    if asked is None:
        asked = _tls.asked = {}
    return asked


def _on_scalar(event: str, _value, **_kw) -> None:
    # A trace or a lowering begins.  jnp's own functions are jitted, so the
    # trace of a step begins thousands of inner traces and its lowering
    # hundreds more (a lowering rule traces what it is written in): only the
    # outermost of them is a phase, and holds the others' time.
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        _tls.depth = getattr(_tls, "depth", 0) + 1


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    # JAX stamps these with time.time(); the phases are on monotonic_s().
    t0, dur, fun = trace.from_wall(start), end - start, str(kw.get("fun_name"))
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        depth = _tls.depth = max(0, getattr(_tls, "depth", 1) - 1)
        if depth:
            return
        if event == _TRACE_EVENT:
            trace.record_phase("jit_trace", t0, dur, fun=fun)
        else:
            trace.record_phase("jit_lower", t0, dur, fun=fun)
    elif event == _COMPILE_EVENT:
        asked = _asked()
        cache = "hit" if "hit" in asked else "miss" if "requested" in asked else "off"
        more = {k: asked[k] for k in ("retrieval_s", "written") if k in asked}
        asked.clear()
        trace.record_phase("xla_compile_or_load", t0, dur, fun=fun, cache=cache, **more)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_REQUEST:
        _asked()["requested"] = True
    elif event == _CACHE_HIT:
        _asked()["hit"] = True
    elif event == _CACHE_WRITTEN:
        _asked()["written"] = True


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _CACHE_RETRIEVAL:
        _asked()["retrieval_s"] = seconds


def _install_compile_listeners() -> None:
    """Once a process.  The listeners run only when JAX traces, lowers or
    compiles something: a warm step never reaches them."""
    global _listeners_installed
    if _listeners_installed:
        return
    _listeners_installed = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_stats() -> dict:
    """What this process has built so far, summed from the phases:
    ``requests`` programs went through the persistent cache, ``hits`` were
    loaded from it (``load_s`` seconds of retrieval), ``misses`` were not
    and were compiled now, ``written`` of those were stored for the next
    process (JAX stores what took a second or more to compile unless told
    otherwise); ``compile_s`` is the compiler's time over every program
    that did not hit, the cache asked or not; ``trace_s`` and ``lower_s``
    are self times, so a program an eager call builds inside a trace counts
    once.  A jit whose executable is already in this process's memory
    raises no event, and what a process builds after ``trace.MAX_PHASES``
    phases is not counted (``trace.phases_dropped()``)."""
    snapshot = trace.phases()
    self_s = trace.self_times(snapshot)
    out = {"requests": 0, "hits": 0, "misses": 0, "written": 0,
           "trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0, "compile_s": 0.0}
    for p in snapshot:
        if p.name == "jit_trace":
            out["trace_s"] += self_s[p.id]
        elif p.name == "jit_lower":
            out["lower_s"] += self_s[p.id]
        elif p.name == "xla_compile_or_load":
            cache = p.args["cache"]
            out["requests"] += cache != "off"
            out["hits"] += cache == "hit"
            out["misses"] += cache == "miss"
            out["written"] += bool(p.args.get("written"))
            if cache == "hit":
                out["load_s"] += p.args.get("retrieval_s", 0.0)
            else:
                out["compile_s"] += p.dur
    return out


def announce_devices(entry: str) -> None:
    """Print ``<entry>: platform=… device_kind=… devices=N`` — the device
    as JAX reports it (this initializes the backend).  Call before
    building models, pipelines or engines."""
    import jax

    with trace.phase("backend_init"):
        devices = jax.devices()
    print(
        f"{entry}: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} devices={len(devices)}",
        flush=True,
    )
