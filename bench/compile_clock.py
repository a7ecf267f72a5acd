"""Compilations and their seconds, from JAX's monitoring events.

A copy of ``chip_smoke.py``'s ``CompileClock`` kept with the benchmark.  A
program that needed a new shape is counted once whether XLA compiled it
or read it from the persistent cache, so a window that meets a shape it
did not warm up shows it.
"""
from __future__ import annotations


class CompileClock:
    def __init__(self, jax) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def programs(self) -> int:
        """Programs made ready: compiled, or loaded from the cache."""
        return self.compiles + self.cache_hits
