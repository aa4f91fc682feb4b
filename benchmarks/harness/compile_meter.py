"""Sums JAX's own compile events. Copied from ``chip_smoke.py::_CompileMeter``
(sound there; the original is listed in PERF.md for a later PR to fold).

``backend_compile_duration`` covers a compile and also a retrieval from the
persistent cache; ``compiles`` counts both, so a non-zero delta inside the
measured window means a shape was not warmed, whether or not the cache
served it.
"""


class CompileMeter:
    """Register once per process, before the first jit; read deltas."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    @staticmethod
    def delta(after, before):
        return {k: after[k] - before[k] for k in after}
