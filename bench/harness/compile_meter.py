"""Counts the programs JAX builds, from its own monitoring events.

A copy of ``chip_smoke.py``'s ``CompileMeter``.  Its backend-compile event
fires for every program JAX builds for the device, whether compiled or read
back from the persistent compilation cache; the cache's own hit event tells
the two apart.  A jit cache that an engine clears (``DECODE_SHAPE_LIMIT``)
rebuilds its programs from the persistent cache, and that costs the window
too, so both count.
"""
from __future__ import annotations


class CompileMeter:
    def __init__(self):
        import jax.monitoring

        self.built = 0          # programs compiled or read from the cache
        self.cache_loads = 0    # of those, read from the persistent cache
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, *args, **kwargs) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.built += 1

    def _on_event(self, event: str, *args, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    @property
    def compiles(self) -> int:
        return self.built - self.cache_loads
