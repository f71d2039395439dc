"""Backend compiles counted from JAX's monitoring events.

Copied from ``chip_smoke.py``'s ``CompileMeter``.  JAX reports a backend
compile event for every executable it makes, also where the persistent
cache supplies it (then with the retrieval's time), and a cache-hit or
cache-miss event beside it.  ``compiles`` in a window counts both kinds: a
new executable inside the measured window is a fault either way.
"""

from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
