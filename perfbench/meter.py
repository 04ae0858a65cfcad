"""Compile seconds and persistent-cache hits/misses, from JAX's own
monitoring events (every compile of the process, not only stage
programs).  Copy of ``chip_smoke.Meter`` (PERF.md, Open questions)."""

from __future__ import annotations


class Meter:
    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "compiles": self.compiles, "compile_s": self.compile_s}
