"""What jax compiled or loaded, from its own monitoring events.

The method of ``chip_smoke.CompileMeter``, copied so that the benchmark
counts compilations by itself: every program jax built or loaded from its
persistent cache, with the seconds spent, and the cache's hits and misses.
A program may compile on a thread of its own (a serving lane), hence the lock.
"""
from __future__ import annotations

import threading
from typing import Dict

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class CompileMeter:
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._n = {"programs": 0, "compile_s": 0.0, "cache_hits": 0,
                   "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == _COMPILE:
            with self._lock:
                self._n["programs"] += 1
                self._n["compile_s"] += secs

    def _event(self, name: str, **_kw) -> None:
        key = {_HIT: "cache_hits", _MISS: "cache_misses"}.get(name)
        if key:
            with self._lock:
                self._n[key] += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._n)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
