"""Compilations, from JAX's own monitoring events: backend compiles (a
persistent-cache hit's retrieval counts as one), their seconds and the
functions compiled, traces, and persistent-cache hits and misses."""
from __future__ import annotations

import os


class CompileLog:
    def __init__(self, jax):
        self.compiles = 0
        self.traces = 0
        self.seconds = 0.0
        self.names: list[str] = []
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, fun_name=None, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            self.names.append(str(fun_name))
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            key = name.rsplit("/", 1)[1]
            self.events[key] = self.events.get(key, 0) + 1

    def mark(self) -> tuple:
        return (self.compiles, self.traces, len(self.names))

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "traces": self.traces - mark[1],
                "names": sorted(set(self.names[mark[2]:]))}

    def summary(self) -> str:
        return (f"compiles={self.compiles} compile_s={self.seconds:.3f} "
                f"cache_hits={self.events.get('cache_hits', 0)} "
                f"cache_misses={self.events.get('cache_misses', 0)}")


def use_cache(jax, root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key, so it never moves); every program
    is cached, however quickly it compiled."""
    path = os.path.join(root, ".jax_cache", "bench")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
