"""Compile seconds and persistent-cache hits, from JAX's monitoring
events (a copy of ``chip_smoke.PhaseClock``'s listeners). JAX keeps
listeners for the life of the process, so one clock is registered once
and read as differences."""
from __future__ import annotations

import threading

_CLOCK = None


class CompileClock:
    def __init__(self):
        from jax import monitoring
        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration
                self.compiles += 1

    def _event(self, event, **kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def read(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "hits": self.hits, "misses": self.misses}


def clock() -> CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


def since(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}
