"""Caching utilities: the one resolver that places JAX's persistent
compilation cache, and a small instrumented LRU used for per-shape derived
objects.

A cold compile of the fused train step takes minutes on the chip; a warm
on-disk cache keeps it out of every later run. The cache directory is part
of the cache key, so it must not move between runs: it is either the one
the environment names (``JAX_COMPILATION_CACHE_DIR``, read by jax itself)
or ``<checkout>/.jax_cache``.
"""
import os
from collections import OrderedDict


class LRUCache:
    """Bounded mapping with least-recently-used eviction and hit/miss
    counters (the counters feed capacity tuning: a hot cache with a high
    miss rate wants a bigger capacity, one with zero hits wants deleting).

    ``capacity=0`` disables storage entirely — every get is a miss, every
    put a no-op — so callers can hard-off a cache from config without
    branching at each call site. Keys must be hashable.
    """

    def __init__(self, capacity: int):
        if capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {capacity}")
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self._data = OrderedDict()

    def __len__(self):
        return len(self._data)

    def __contains__(self, key):
        return key in self._data

    def get(self, key, default=None):
        """Value for ``key`` (refreshing its recency), else ``default``."""
        try:
            value = self._data[key]
        except KeyError:
            self.misses += 1
            return default
        self._data.move_to_end(key)
        self.hits += 1
        return value

    def put(self, key, value) -> None:
        """Insert/overwrite ``key``, evicting the LRU entry past capacity."""
        if self.capacity == 0:
            return
        if key in self._data:
            self._data.move_to_end(key)
        self._data[key] = value
        while len(self._data) > self.capacity:
            self._data.popitem(last=False)

    def keys(self):
        """Keys in eviction order: least-recently-used first."""
        return list(self._data.keys())

    def clear(self) -> None:
        self._data.clear()

    def stats(self) -> dict:
        return {"size": len(self._data), "capacity": self.capacity,
                "hits": self.hits, "misses": self.misses}


# programs that compile faster than this are not worth a cache file (jax's
# own default is 1.0 s); only applied when this module places the cache
_MIN_COMPILE_SECS = 0.5


def repo_cache_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def resolve_compile_cache() -> str:
    """Place the persistent XLA compile cache; returns the directory in
    effect. THE one call site of ``jax_compilation_cache_dir`` in the repo.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, so nothing is
    set here — whoever launched the process owns the placement. Unset: the
    cache goes to ``<checkout>/.jax_cache``, a fixed path (the directory is
    part of the cache key; one that moves never hits). Idempotent: every
    entry point (engine.train, ServingEngine, bench.py, chip_smoke.py, the
    test harness) calls it and resolves the same directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    import jax

    cache_dir = repo_cache_dir()
    if jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          _MIN_COMPILE_SECS)
    return cache_dir
