"""Count the compilations JAX's persistent cache did not serve."""

from __future__ import annotations


class CompileCounter:
    """Listens to JAX's own monitoring events: every compile request that
    consults the persistent cache, and every hit. `unserved` is their
    difference: programs compiled in this process. Inside a timed window
    it has to be 0 (every shape was warmed up); re-tracing and fetching an
    executable from the cache is the program's own work and is a hit."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    @property
    def unserved(self) -> int:
        return self.requests - self.hits
