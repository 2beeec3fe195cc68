"""Backend compile seconds and persistent-cache traffic from jax's own
monitoring events (the benchmark's copy of ``chip_smoke._CompileStats``)."""

from __future__ import annotations


class CompileStats:
    def __init__(self):
        import jax.monitoring as mon

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def snapshot(self):
        """``compiles`` counts backend compile calls (a persistent-cache hit
        is one too, a short one); ``misses`` those the cache did not serve."""
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
