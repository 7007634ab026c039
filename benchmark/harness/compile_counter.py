"""Counts this process's XLA compile requests (copy of chip_smoke.py's
``CompileCounter``; the original stays with the smoke)."""

from __future__ import annotations


class CompileCounter:
    """``requests`` go through the persistent cache, ``hits`` were loaded
    from it; requests - hits = programs compiled now.  A jit whose
    executable is already in this process's memory raises no event, so a
    warm window counts 0 requests."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
    }
    _COMPILE_SECONDS = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.counts = {"requests": 0, "hits": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._COMPILE_SECONDS:
            self.compile_s += duration

    def snapshot(self) -> dict:
        return {**self.counts, "compile_s": self.compile_s}
