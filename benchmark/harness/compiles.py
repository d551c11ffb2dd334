"""Counts what JAX compiles: backend compiles (a program built, or loaded
from the persistent cache), the seconds they took, and the persistent
cache's hits. Copied in idea from `chip_smoke.Compiles`, on JAX's own
monitoring events instead of the program's observability registry."""
import jax


class Compiles:
    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **kw):
        if name == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif name == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1

    def _on_duration(self, name, secs, **kw):
        if name.endswith('backend_compile_duration'):
            self.count += 1
            self.seconds += secs

    def facts(self):
        return {'compiles': self.count,
                'compile_seconds': round(self.seconds, 3),
                'cache_hits': self.cache_hits,
                'cache_misses': self.cache_misses}
