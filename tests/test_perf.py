"""Tier-1 tests for mosaic_tpu.perf: shape bucketing, the process
kernel cache, and the double-buffered streaming executor.

The load-bearing assertions:

* bucket-boundary parity — the padded/jitted classify path must agree
  bit-for-bit with the interpreted numpy fallback at sizes 1 below, at,
  and 1 above a pow2 bucket edge (padding bugs live exactly there);
* recompile-storm guard — running the identical tessellate+join
  workload twice must add ZERO kernel-cache misses and ZERO XLA
  backend compiles the second time (one compile per (bucket, kernel),
  ever, is the whole point of the policy);
* pipeline ordering — chunk results come back in input order even
  though fetch/consume runs on a worker thread, and an injected fault
  in the worker propagates to the caller instead of hanging the pool.
"""

import numpy as np
import pytest

from mosaic_tpu import read_wkt
from mosaic_tpu.core.index.custom import CustomIndexSystem, GridConf
from mosaic_tpu.core import tessellate as tess
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.perf.bucketing import (iter_size_buckets, pad_rows,
                                       pad_to_block, pow2_bucket)
from mosaic_tpu.perf.jit_cache import JitCache, kernel_cache
from mosaic_tpu.perf.pipeline import chunk_rows, donate_jit, stream
from mosaic_tpu.resilience.faults import InjectedFault


@pytest.fixture(scope="module")
def grid():
    return CustomIndexSystem(GridConf(0, 16, 0, 16, 2, 1.0, 1.0))


# --------------------------------------------------------- bucketing

def test_pow2_bucket_policy():
    assert pow2_bucket(1) == 4          # floor stops 1/2-wide compiles
    assert pow2_bucket(4) == 4
    assert pow2_bucket(5) == 8
    assert pow2_bucket(1000) == 1024
    assert pow2_bucket(1024) == 1024
    assert pow2_bucket(1025) == 2048
    assert pow2_bucket(3, floor=16) == 16
    assert pow2_bucket(100_000, cap=8192) == 8192


def test_iter_size_buckets_partition():
    sizes = np.array([3, 5, 9, 4, 17, 8, 1])
    seen = []
    for width, idx in iter_size_buckets(sizes, floor=4):
        assert np.all(sizes[idx] <= width)
        # width is the pow2 bucket of the group's smallest member and
        # every member would land in a bucket <= width
        assert width == pow2_bucket(sizes[idx].min(), floor=4)
        seen.extend(idx.tolist())
    # exact partition: every item exactly once
    assert sorted(seen) == list(range(len(sizes)))
    # deterministic: same input -> same grouping
    a = [(w, i.tolist()) for w, i in iter_size_buckets(sizes, floor=4)]
    b = [(w, i.tolist()) for w, i in iter_size_buckets(sizes, floor=4)]
    assert a == b


def test_pad_rows_and_pad_to_block():
    a = np.arange(6, dtype=np.float64).reshape(3, 2)
    p = pad_rows(a, 5, np.inf)
    assert p.shape == (5, 2)
    assert np.array_equal(p[:3], a)
    assert np.all(np.isinf(p[3:]))
    assert pad_rows(a, 3) is a          # no copy when already sized
    with pytest.raises(ValueError):
        pad_rows(a, 2)
    m = np.ones(3, dtype=bool)
    pa, pm, n = pad_to_block(8, a, m, fills=[0.0, False])
    assert n == 3 and pa.shape == (8, 2) and pm.shape == (8,)
    assert not pm[3:].any()


@pytest.mark.parametrize("P", [255, 256, 257])
def test_pair_check_parity_at_bucket_boundary(P, monkeypatch):
    """Jitted pair-check == numpy fallback at the pow2 bucket edge
    (floor=256): the padded rows must never leak into the result."""
    rng = np.random.default_rng(P)
    K = 6
    a1 = rng.uniform(0, 10, (P, K, 2))
    b1 = np.roll(a1, -1, axis=1)
    a2 = rng.uniform(0, 10, (P, 2))
    b2 = rng.uniform(0, 10, (P, 2))
    vmask = rng.random((P, K)) > 0.3
    vmask[:, 0] = True                  # no all-invalid rows
    hit_j, in_j = tess._pair_check(a1, b1, a2, b2, vmask)
    monkeypatch.setattr(tess, "_f64_jit_enabled",
                        lambda *a, **k: False)
    hit_n, in_n = tess._pair_check(a1, b1, a2, b2, vmask)
    assert np.array_equal(hit_j, hit_n)
    assert np.array_equal(in_j, in_n)


def test_tessellate_parity_jit_vs_numpy(grid, monkeypatch):
    """End-to-end: the bucketed/jitted tessellation equals the
    interpreted numpy path chip-for-chip on concave + holed input.

    (Coordinates avoid polygon edges grazing cell corners exactly —
    at such zero-area degeneracies the two float paths may round a
    sliver chip in or out differently, which is not a padding bug.)"""
    wkt = ["POLYGON ((1.31 1.73, 6.83 2.12, 5.91 6.34, 2.23 5.81,"
           " 1.31 1.73))",
           "POLYGON ((0.5 8.5, 7.5 8.5, 7.5 15.5, 0.5 15.5, 0.5 8.5),"
           " (2.5 10.5, 5.5 10.5, 5.5 13.5, 2.5 13.5, 2.5 10.5))"]
    arr = read_wkt(wkt)
    chips_jit = tessellate(arr, 1, grid)
    monkeypatch.setattr(tess, "_f64_jit_enabled",
                        lambda *a, **k: False)
    chips_np = tessellate(arr, 1, grid)
    assert np.array_equal(chips_jit.cell_id, chips_np.cell_id)
    assert np.array_equal(chips_jit.geom_id, chips_np.geom_id)
    assert np.array_equal(chips_jit.is_core, chips_np.is_core)


# ------------------------------------------------------ kernel cache

def test_jit_cache_hit_miss_eviction():
    cache = JitCache(capacity=2)
    built = []

    def builder(tag):
        def build():
            built.append(tag)
            return lambda: tag
        return build

    assert cache.get_or_build("k", 1, builder("a"))() == "a"
    assert cache.get_or_build("k", 1, builder("a2"))() == "a"  # hit
    assert cache.stats() == {"hits": 1, "misses": 1, "evictions": 0,
                             "size": 1}
    cache.get_or_build("k", 2, builder("b"))
    cache.get_or_build("k", 3, builder("c"))      # evicts key 1 (LRU)
    assert cache.stats()["evictions"] == 1
    assert len(cache) == 2
    # key 1 was evicted: rebuilding it is a miss again
    assert cache.get_or_build("k", 1, builder("a3"))() == "a3"
    assert built == ["a", "b", "c", "a3"]
    # same key, different kernel name = different entry
    cache2 = JitCache()
    cache2.get_or_build("x", 1, builder("x1"))
    assert cache2.get_or_build("y", 1, builder("y1"))() == "y1"


def test_no_recompile_on_second_identical_run(grid):
    """Recompile-storm assertion: the flagship-shaped workload
    (tessellate + jitted PIP join) compiles once per (bucket, kernel)
    — an identical second pass adds zero kernel-cache misses and zero
    XLA backend compiles."""
    import jax
    import jax.numpy as jnp
    from mosaic_tpu.obs import install_jax_listeners, metrics, tracer
    from mosaic_tpu.parallel.pip_join import (build_pip_index, localize,
                                              make_pip_join_fn)
    install_jax_listeners()
    was_enabled = tracer.enabled
    tracer.enable()
    kernel_cache.clear()
    s0 = kernel_cache.stats()           # counters are cumulative:
    m0 = metrics.counter_value("perf/jit_cache/miss")   # use deltas
    try:
        arr = read_wkt(
            ["POLYGON ((1.3 1.7, 6.8 2.1, 5.9 6.3, 2.2 5.8, 1.3 1.7))",
             "POLYGON ((8.5 8.5, 14.5 9.1, 13.9 14.3, 9.2 13.8,"
             " 8.5 8.5))"])
        rng = np.random.default_rng(5)
        pts = rng.uniform(0, 16, (20_000, 2))

        chips = tessellate(arr, 1, grid)
        idx = build_pip_index(arr, 1, grid, chips=chips)
        join = jax.jit(make_pip_join_fn(idx, grid))
        ploc = jnp.asarray(localize(idx, pts))
        jax.block_until_ready(join(ploc))

        s1 = kernel_cache.stats()
        r1 = metrics.counter_value("jax/recompiles")
        m1 = metrics.counter_value("perf/jit_cache/miss")
        # one compile per (bucket, kernel): every miss minted exactly
        # one distinct cache entry, and the miss counter agrees
        assert s1["misses"] - s0["misses"] == s1["size"]
        assert m1 - m0 == s1["misses"] - s0["misses"]

        tessellate(arr, 1, grid)                 # identical second pass
        jax.block_until_ready(join(ploc))
        s2 = kernel_cache.stats()
        r2 = metrics.counter_value("jax/recompiles")
        assert s2["misses"] == s1["misses"], "kernel cache missed again"
        assert s2["hits"] > s1["hits"]
        assert r2 == r1, "XLA recompiled on an identical second run"
    finally:
        if not was_enabled:
            tracer.disable()


def test_migrated_kernels_warm_zero_compiles():
    """The pre-kernel_cache holdouts (overlay kernels, H3
    candidate-sampling kernel) now build through get_or_build: an
    identical second build must be a cache hit with zero new misses,
    so warm runs stay at zero compiles."""
    from mosaic_tpu.core.index.h3.system import H3IndexSystem
    from mosaic_tpu.parallel.overlay import (make_overlay_fn,
                                             make_overlay_pairs_fn)
    kernel_cache.clear()
    s0 = kernel_cache.stats()           # counters are cumulative:
    make_overlay_fn(4, 4, 8, 8)         # use deltas
    make_overlay_pairs_fn(1024, 8, 8, pair_cap=16)
    s1 = kernel_cache.stats()
    assert s1["misses"] - s0["misses"] == 2
    make_overlay_fn(4, 4, 8, 8)              # identical rebuilds: hits
    make_overlay_pairs_fn(1024, 8, 8, pair_cap=16)
    s2 = kernel_cache.stats()
    assert s2["misses"] == s1["misses"], "overlay kernel rebuilt warm"
    assert s2["hits"] - s1["hits"] == 2
    # the H3 sampling kernel shares one entry per res across index
    # instances (pre-migration it lived in a per-instance dict, so a
    # fresh H3IndexSystem recompiled and the cache counters were blind)
    xy = np.random.default_rng(0).uniform(-40, 40, (40_000, 2))
    H3IndexSystem()._point_to_cell_sample(xy, 5)
    m1 = kernel_cache.stats()["misses"]
    H3IndexSystem()._point_to_cell_sample(xy, 5)   # fresh instance
    assert kernel_cache.stats()["misses"] == m1, \
        "H3 sample kernel recompiled per instance"


@pytest.mark.parametrize("env, arg, want", [
    ("from-env", "from-conf", "from-env"),     # JAX's variable wins
    (None, "from-conf", "from-conf"),          # then the conf key
    (None, None, None),                        # then the checkout
])
def test_persistent_cache_resolution(tmp_path, monkeypatch, env, arg,
                                     want):
    """One resolution: JAX_COMPILATION_CACHE_DIR (the package then sets
    no directory in code), else an explicit/conf directory, else the
    checkout's fixed .jax_cache."""
    import os
    import jax
    from mosaic_tpu.perf import jit_cache as jc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    prev = [getattr(jax.config, k) for k in keys]
    prev_dir = jc._persist_dir
    jc._persist_dir = None
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = jc.configure_persistent_cache(
            str(tmp_path / arg) if arg else None)
        expect = str(tmp_path / want) if want else jc.CHECKOUT_CACHE_DIR
        assert got == expect == jc.persistent_cache_dir()
        assert jax.config.jax_compilation_cache_dir == (
            prev[0] if env else expect)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in zip(keys, prev):
            jax.config.update(k, v)
        jc._persist_dir = prev_dir
    assert jc.CHECKOUT_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


# ---------------------------------------------------------- pipeline

def test_chunk_rows():
    assert chunk_rows(10, 4) == [slice(0, 4), slice(4, 8), slice(8, 10)]
    assert chunk_rows(4, 4) == [slice(0, 4)]
    assert chunk_rows(0, 4) == []
    assert chunk_rows(3, 0) == [slice(0, 1), slice(1, 2), slice(2, 3)]


def test_stream_ordering_and_consume():
    import jax
    import jax.numpy as jnp
    n, chunk = 1000, 128
    x = np.arange(n, dtype=np.float64)
    fn = jax.jit(lambda v: v * 2.0)
    out = np.empty(n)

    def put(sl):
        return jax.device_put(jnp.asarray(x[sl]))

    def consume(i, sl, host):
        out[sl] = host
        return i

    order = stream(chunk_rows(n, chunk), compute=fn, put=put,
                   consume=consume)
    assert order == list(range(len(chunk_rows(n, chunk))))
    assert np.array_equal(out, x * 2.0)
    # without put/consume: raw host outputs, in order
    outs = stream([jnp.asarray(x[sl]) for sl in chunk_rows(n, chunk)],
                  compute=fn)
    assert np.array_equal(np.concatenate(outs), x * 2.0)
    assert stream([], compute=fn) == []


def test_stream_accepts_generator_source():
    """Regression: ``stream`` must accept a LAZY chunk iterator (the
    chip store's scan path) — same results as a list source, pulled at
    most one chunk ahead of the running compute (the double-buffer
    window), and never materialized into a list."""
    import jax
    import jax.numpy as jnp
    n, chunk = 1000, 128
    x = np.arange(n, dtype=np.float64)
    slices = chunk_rows(n, chunk)
    fn = jax.jit(lambda v: v * 2.0)
    pulled = {"n": 0}

    def gen():
        for sl in slices:
            pulled["n"] += 1
            yield sl

    computed = {"n": 0}
    window = []

    def compute(dev):
        computed["n"] += 1
        # bounded look-ahead: at the i-th compute, the source has
        # yielded at most i chunks plus the one-ahead stage
        window.append(pulled["n"] - computed["n"])
        return fn(dev)

    out = np.empty(n)

    def consume(i, sl, host):
        out[sl] = host
        return i

    order = stream(gen(), compute=compute,
                   put=lambda sl: jax.device_put(jnp.asarray(x[sl])),
                   consume=consume)
    assert order == list(range(len(slices)))
    assert np.array_equal(out, x * 2.0)
    assert max(window) <= 1        # never more than one chunk ahead
    # an exhausted-immediately generator is the empty stream
    assert stream((s for s in []), compute=fn) == []


def test_donate_jit_cpu_gating():
    """On CPU the wrapper must NOT request donation (the backend
    ignores it and warns per launch) — the same buffer stays usable
    across launches."""
    import jax
    import jax.numpy as jnp
    import warnings
    assert jax.devices()[0].platform == "cpu"
    fn = donate_jit(lambda v: v + 1.0, donate_argnums=(0,))
    buf = jnp.arange(4.0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a donation warning would raise
        a = fn(buf)
        b = fn(buf)                     # buffer NOT invalidated on cpu
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_stream_fault_propagates(fault_plan):
    """An injected fault on the worker thread surfaces to the caller
    (no hang, no silently dropped chunk); once the plan is exhausted
    the same pipeline runs clean."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda v: v * 3.0)
    chunks = [jnp.ones(8) * i for i in range(4)]
    fault_plan("seed=7;site=pipeline.fetch,fails=1")
    with pytest.raises(InjectedFault):
        stream(chunks, compute=fn)
    # plan exhausted -> the identical pipeline now completes in order
    outs = stream(chunks, compute=fn)
    for i, o in enumerate(outs):
        assert np.array_equal(o, np.ones(8) * i * 3.0)


@pytest.mark.parametrize("chunk", [
    2048,       # ragged last chunk
    16384,      # one chunk larger than the batch
    10_037,     # one chunk exactly the batch
])
def test_streamed_pip_join_matches_unstreamed(grid, chunk):
    """The chunked double-buffered join returns the same zones as the
    one-launch join + host recheck (chunking must not change results,
    whether the last chunk is ragged or one chunk holds the batch)."""
    import jax
    import jax.numpy as jnp
    from mosaic_tpu.parallel.pip_join import (build_pip_index,
                                              host_recheck_fn, localize,
                                              make_pip_join_fn,
                                              make_streamed_pip_join)
    arr = read_wkt(
        ["POLYGON ((1.3 1.7, 6.8 2.1, 5.9 6.3, 2.2 5.8, 1.3 1.7))",
         "POLYGON ((8.5 1.5, 14.5 1.5, 14.5 6.5, 8.5 6.5, 8.5 1.5))"])
    chips = tessellate(arr, 1, grid)
    idx = build_pip_index(arr, 1, grid, chips=chips)
    rng = np.random.default_rng(11)
    pts = rng.uniform(0, 16, (10_037, 2))
    join = jax.jit(make_pip_join_fn(idx, grid))
    z, u = join(jnp.asarray(localize(idx, pts)))
    ref = host_recheck_fn(idx, arr)(pts, np.asarray(z).copy(),
                                    np.asarray(u))
    sjoin = make_streamed_pip_join(idx, grid, polys=arr, chunk=chunk)
    zs, rechecked = sjoin(pts)
    assert np.array_equal(zs, ref)
    assert rechecked >= 0
