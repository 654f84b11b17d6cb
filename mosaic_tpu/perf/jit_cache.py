"""Process-level compiled-kernel cache + persistent compilation cache.

Two layers, addressing two different compile costs:

* :class:`JitCache` — an LRU of ``jax.jit``-wrapped callables keyed on
  ``(kernel name, shape/dtype/static-arg key)``.  It unifies the
  ad-hoc module/instance ``dict`` caches that had accumulated in
  ``core/tessellate.py`` (``_PARITY_JIT``/``_CLIP_JIT``),
  ``models/knn.py`` (``SpatialKNN._step_cache``) and
  ``parallel/raster_halo.py`` (``_JIT_CACHE``) — one bounded cache,
  one eviction policy, one set of hit/miss/eviction counters in
  ``obs.metrics`` (``perf/jit_cache/hit|miss|evict`` plus per-kernel
  ``.../miss/<name>``).  The counters also accumulate locally so tests
  can assert on them without enabling the registry.
* :func:`configure_persistent_cache` — wires JAX's on-disk compilation
  cache (``jax_compilation_cache_dir``) with thresholds dropped to
  zero so every entry persists.  A warm-started process then loads
  compiled executables from disk instead of re-running XLA: the
  first-call warmup disappears.  NOTE: ``jax.monitoring`` still fires
  ``backend_compile`` duration events on persistent-cache HITS (the
  event wraps the lookup), so "did anything actually compile" must be
  read from the ``jax/cache/cache_misses`` counter
  (``obs.jaxmon._on_event``), not from ``jax/recompiles`` — the bench
  record and the CI warm-start assertion both do.

The configuration must be identical and applied BEFORE the first
compile in every process that shares a cache directory: the cache key
hashes the compile options, so config drift between runs silently
turns hits into misses.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional

from ..obs.metrics import metrics

__all__ = ["JitCache", "kernel_cache", "configure_persistent_cache",
           "persistent_cache_dir"]

#: JAX's own cache-directory variable: when it is set, JAX reads it
#: itself and this module sets no directory in code
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when neither the variable nor the
#: ``mosaic.jit.cache.dir`` conf key places it: a fixed path inside the
#: checkout (the path is part of the cache key, so it must not move)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class JitCache:
    """Bounded LRU of compiled functions, thread-safe.

    Keys are ``(name, key)`` where ``name`` identifies the kernel
    builder (a stable string, NOT a function id — ids recycle) and
    ``key`` captures everything the compiled artifact depends on:
    padded shapes, dtypes, static arguments, and — for sharded
    kernels — ``id(mesh)`` (a jitted fn bakes its mesh's shardings).
    """

    def __init__(self, capacity: int = 256, scope: str = "kernel"):
        self.capacity = int(capacity)
        self.scope = scope
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, Callable]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, name: str, key,
                     build: Callable[[], Callable]) -> Callable:
        """Return the cached callable for ``(name, key)``, building
        (and caching) it on first use.  ``build`` runs outside the
        lock-free fast path but inside the miss path's lock — builders
        are cheap ``jax.jit(...)`` wrappings (compilation itself is
        lazy, at first call of the returned fn)."""
        full = (name, key)
        with self._lock:
            fn = self._entries.get(full)
            if fn is not None:
                self._entries.move_to_end(full)
                self.hits += 1
                if metrics.enabled:
                    metrics.count("perf/jit_cache/hit")
                return fn
            fn = self._instrument(name, build())
            self._entries[full] = fn
            self.misses += 1
            if metrics.enabled:
                metrics.count("perf/jit_cache/miss")
                metrics.count(f"perf/jit_cache/miss/{name}")
            try:
                # seed a kernel-ledger row so every named cache entry
                # shows up in profiler reports even before its first
                # observed launch (lazy import: perf must not require
                # the profiler at import time)
                from ..obs.profiler import ledger
                ledger.register(name, key)
            except Exception:
                pass
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
                if metrics.enabled:
                    metrics.count("perf/jit_cache/evict")
        return fn

    @staticmethod
    def _instrument(name: str, fn: Callable) -> Callable:
        """Wrap a freshly built kernel so each launch notes its output
        bytes with the device-memory ledger (``memwatch``) as a
        transient under ``jit/<name>`` — the attribution feed that
        gives every cached operator (not just the streamed paths) a
        per-trace peak-bytes figure.  Fully fenced: ledger trouble
        never reaches the kernel, and non-callable cache entries pass
        through untouched."""
        if not callable(fn):
            return fn

        def _launch(*args, **kwargs):
            out = fn(*args, **kwargs)
            try:
                from ..obs.memwatch import memwatch
                if memwatch.enabled:
                    import jax
                    nb = sum(int(getattr(leaf, "nbytes", 0)) for leaf
                             in jax.tree_util.tree_leaves(out))
                    if nb:
                        memwatch.note_transient(f"jit/{name}", nb)
            except Exception:
                pass
            return out

        return _launch

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "size": len(self._entries)}


#: the process-global kernel cache every bucketed kernel goes through
kernel_cache = JitCache()


_persist_lock = threading.Lock()
_persist_dir: Optional[str] = None


def persistent_cache_dir() -> Optional[str]:
    """The directory the persistent compilation cache was wired to in
    this process (None = not configured)."""
    return _persist_dir


def configure_persistent_cache(path: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache.

    Resolution order: ``JAX_COMPILATION_CACHE_DIR`` (JAX reads it
    itself; no directory is set in code) > explicit argument (the
    ``mosaic.jit.cache.dir`` conf key passes one) >
    :data:`CHECKOUT_CACHE_DIR`.  Importing the package calls this once,
    so every process has the cache.  Returns the directory in use.
    Idempotent; re-pointing at a different directory is honored (last
    call wins) and logged to the flight recorder.  JAX creates the
    directory at its first write.

    Thresholds are dropped so EVERY compile persists
    (``min_entry_size_bytes=-1``, ``min_compile_time_secs=0``): this
    package's kernels are many and individually fast to compile, and
    the 1-2 ms disk hit beats even the cheapest recompile.  Call this
    before the first compile with the SAME settings in every process
    sharing the directory — the cache key hashes compile options, so
    drift turns hits into misses."""
    global _persist_dir
    import jax
    env = os.environ.get(JAX_CACHE_DIR_ENV)
    path = str(env or path or CHECKOUT_CACHE_DIR)
    with _persist_lock:
        if _persist_dir == path:
            return _persist_dir
        if not env:
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        _persist_dir = path
    from ..obs.recorder import recorder
    recorder.record("config", key="mosaic.jit.cache.dir", value=path)
    return _persist_dir
