"""Performance layer: shape bucketing, kernel caching, streaming.

The three ingredients of the modern-hardware recipe (adaptive
geospatial joins, arxiv 1802.09488; pipelined device joins, 3DPipe)
applied to the chipping/join hot path:

* ``perf.bucketing`` — one shared power-of-2 padding policy for every
  ragged batch (polygon edge counts, ring sizes, pair blocks), so each
  variable-length workload compiles **once per bucket** instead of
  re-tracing per shape.
* ``perf.jit_cache`` — the process-level compiled-kernel LRU unifying
  the ad-hoc ``dict`` caches that had grown in ``core/tessellate.py``,
  ``models/knn.py`` and ``parallel/raster_halo.py``, plus the wiring
  for JAX's **persistent** compilation cache (env
  ``JAX_COMPILATION_CACHE_DIR``, else conf key ``mosaic.jit.cache.dir``,
  else the checkout's ``.jax_cache/``) so the
  first-call compile cost vanishes on warm starts.  Hit/miss/eviction
  counters land in ``obs.metrics`` under ``perf/jit_cache/*``.
* ``perf.pipeline`` — a double-buffered chunk executor: host→device
  transfer of chunk N+1 overlaps device compute on chunk N, and the
  host-side consumption (f64 recheck, re-rank) of chunk N−1 runs on a
  worker thread.  Used by the streamed PIP join, the KNN brute-force
  top-k and the multi-tile raster halo convolve.
* ``perf.fusion`` — whole-query fusion for the SQL engine: adjacent
  size-class-compatible operators (filter → project/aggregate) compile
  into ONE jitted XLA program keyed into ``kernel_cache`` as
  ``fused:<opset>:<sig>``, with zero intermediate host transfers and
  bit-for-bit parity with the unfused path.  Planner-gated per query
  (``decide_fusion``, conf ``mosaic.fusion.enabled``).  Imported
  lazily by ``sql.planner`` — not re-exported here.
"""

from __future__ import annotations

from .bucketing import (iter_size_buckets, pad_rows, pad_to_block,
                        pow2_bucket)
from .jit_cache import (JitCache, configure_persistent_cache,
                        kernel_cache, persistent_cache_dir)
from .pipeline import chunk_rows, donate_jit, stream

__all__ = [
    "pow2_bucket", "iter_size_buckets", "pad_rows", "pad_to_block",
    "JitCache", "kernel_cache", "configure_persistent_cache",
    "persistent_cache_dir",
    "stream", "chunk_rows", "donate_jit",
]
