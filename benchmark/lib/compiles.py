"""Compile events and persistent-cache hits from jax's own monitoring
(copied from chip_smoke.py's ``Compiles``, which is sound; the original is
listed under Open questions in PERF.md)."""

from __future__ import annotations


class Compiles:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self.secs = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "programs": self.programs,
                "cache_hits": self.hits, "cache_misses": self.misses}
