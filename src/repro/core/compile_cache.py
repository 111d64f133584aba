"""Bounded, instrumented compile caches — the memory contract of a
long-lived process.

Every compiled artifact this repo memoizes (engine step/segment
executables, sims behavior objects, ensemble runners) goes through a
:class:`CompiledCache`: an LRU-bounded mapping with hit / miss / eviction
counters registered in a process-wide registry.  A serving process that
lives for days must not leak executables — ``functools.lru_cache`` bounds
them but hides the churn; these caches expose it, so the scenario server
can report cache behavior per family (docs/serving.md) and a bench row can
pin the hit rate.

Two entry points:

* :func:`memoize` — drop-in decorator replacing ``functools.lru_cache``
  for the engine/sims factories (same hashable-args keying, plus
  ``cache_clear``/``__wrapped__`` for compatibility).
* ``CompiledCache.get_or_build(key, builder)`` — explicit keying for
  callers that compute their own family fingerprint (core.ensemble).

``cache_stats()`` snapshots every registered cache; ``reset_stats()``
zeroes the counters without dropping entries (benchmarks isolate phases
with it).

Across processes the executables live in JAX's persistent compilation
cache, which :func:`enable_persistent_cache` places.  Entry points
(``launch/simulate.py``, ``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call it before their first compile; importing the
library never turns it on.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import pathlib
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

# <repo>/.jax_cache: fixed, so a later process finds what this one compiled
# (the directory is part of the cache key; a moving one never hits).
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_REGISTRY: "OrderedDict[str, CompiledCache]" = OrderedDict()
_REGISTRY_LOCK = threading.Lock()


@dataclasses.dataclass
class CacheStats:
    """Counter snapshot of one cache (cumulative since the last reset)."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    size: int = 0
    maxsize: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": self.size,
                "maxsize": self.maxsize,
                "hit_rate": round(self.hit_rate, 4)}


class CompiledCache:
    """LRU-bounded cache with instrumentation, safe under concurrent
    access (the scenario server builds runners from worker threads).

    The builder runs *outside* the lock — compiling an executable can take
    seconds and must not serialize unrelated lookups.  Two threads racing
    on the same missing key may both build; the first insertion wins and
    the loser's artifact is dropped (JAX compilation is pure, so this is
    only wasted work, never wrong results).
    """

    def __init__(self, name: str, maxsize: int = 64):
        if maxsize < 1:
            raise ValueError(f"CompiledCache maxsize must be >= 1, "
                             f"got {maxsize}")
        self.name = name
        self.maxsize = int(maxsize)
        self._data: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        with _REGISTRY_LOCK:
            _REGISTRY[name] = self

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._data

    def get_or_build(self, key, builder: Callable[[], Any]) -> Any:
        with self._lock:
            if key in self._data:
                self._hits += 1
                self._data.move_to_end(key)
                return self._data[key]
            self._misses += 1
        value = builder()
        with self._lock:
            if key in self._data:          # lost a build race: keep winner
                self._data.move_to_end(key)
                return self._data[key]
            self._data[key] = value
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)
                self._evictions += 1
        return value

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def stats(self) -> CacheStats:
        with self._lock:
            return CacheStats(hits=self._hits, misses=self._misses,
                              evictions=self._evictions,
                              size=len(self._data), maxsize=self.maxsize)

    def reset_stats(self) -> None:
        with self._lock:
            self._hits = self._misses = self._evictions = 0


def memoize(name: str, maxsize: int = 64) -> Callable:
    """``functools.lru_cache`` replacement backed by a registered
    :class:`CompiledCache` (positional-args keying; kwargs are folded in
    as a sorted items tuple, so equivalent calls share an entry)."""

    def deco(fn: Callable) -> Callable:
        cache = CompiledCache(name, maxsize=maxsize)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            key = args if not kwargs \
                else args + (("__kw__",) + tuple(sorted(kwargs.items())),)
            return cache.get_or_build(key, lambda: fn(*args, **kwargs))

        wrapper.cache = cache
        wrapper.cache_clear = cache.clear
        wrapper.__wrapped__ = fn
        return wrapper

    return deco


def get_cache(name: str) -> Optional[CompiledCache]:
    with _REGISTRY_LOCK:
        return _REGISTRY.get(name)


def cache_stats(prefix: str = "") -> Dict[str, Dict[str, Any]]:
    """Snapshot of every registered cache (optionally name-filtered) —
    the figure the scenario server's ``stats()`` endpoint reports."""
    with _REGISTRY_LOCK:
        caches: Tuple[Tuple[str, CompiledCache], ...] = tuple(
            _REGISTRY.items())
    return {n: c.stats().as_dict() for n, c in caches
            if n.startswith(prefix)}


def reset_stats(prefix: str = "") -> None:
    with _REGISTRY_LOCK:
        caches = tuple(_REGISTRY.values())
    for c in caches:
        if c.name.startswith(prefix):
            c.reset_stats()


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the place: JAX reads it
    itself and no other path is set here.  Otherwise the cache goes to
    ``<repo>/.jax_cache``.

    A Pallas kernel's Mosaic body is part of the cache key and carries
    the source locations of its trace, so source paths are cut to
    ``repro/...`` (or the file's name): two checkouts of one tree then
    share their entries.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r"^(.*/(?=repro/)|.*/)")
    jax.config.update("jax_enable_compilation_cache", True)
    return path
