"""PIP-join pipeline: single-device and sharded paths vs host float64.

Reference workload: Quickstart PIP join (SURVEY.md §3.2 downstream join);
distribution testing mirrors the reference's local-cluster pattern
(test/SparkSuite.scala local[4]) with the 8-device CPU mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.bench.workloads import build_workload, nyc_points
from mosaic_tpu.parallel.pip_join import (build_pip_index, host_recheck,
                                          localize, make_pip_join_fn,
                                          make_sharded_streamed_pip_join,
                                          make_streamed_pip_join,
                                          pip_host_truth,
                                          zone_histogram)


@pytest.fixture(scope="module")
def workload():
    polys, grid, res = build_workload(n_side=6, res_cells=64)
    idx = build_pip_index(polys, res, grid)
    return polys, grid, res, idx


def _mesh4():
    """4-device mesh carved from the 8 virtual host devices the suite
    pins via XLA_FLAGS (conftest.py) — the ISSUE's multichip-test
    shape without a second process config."""
    return jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))


def test_pip_join_matches_host_f64(workload):
    polys, grid, res, idx = workload
    pts64 = nyc_points(20_000, seed=3)
    fn = jax.jit(make_pip_join_fn(idx, grid))
    zone, unc = fn(jnp.asarray(localize(idx, pts64)))
    zone = host_recheck(pts64, np.asarray(zone), np.asarray(unc), polys)
    truth = pip_host_truth(pts64, polys)
    assert np.array_equal(zone, truth)
    # a partition: everything except boundary-degenerate points matches
    assert np.mean(truth >= 0) > 0.999


def test_pip_join_partition_covers(workload):
    polys, grid, res, idx = workload
    # every cell of the bbox is core or border of some zone
    assert len(idx.core_cells) > 0 and idx.num_chips > 0
    assert idx.max_dup >= 2          # shared boundary cells exist


def test_out_of_domain_points(workload):
    polys, grid, res, idx = workload
    fn = jax.jit(make_pip_join_fn(idx, grid))
    pts = np.array([[-80.0, 40.7], [-74.0, 50.0], [0.0, 0.0]])
    zone, unc = fn(jnp.asarray(localize(idx, pts)))
    assert np.all(np.asarray(zone) == -1)


@pytest.mark.parametrize("n_dev", [4, 8])
def test_sharded_streamed_parity(workload, n_dev):
    """The sharded streamed flagship path (bucketed padding + slot
    placement + mesh sharding) is bit-for-bit the single-device
    streamed join, including a ragged final chunk not divisible by
    the device count, on a 4-device and the full 8-device mesh."""
    polys, grid, res, idx = workload
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    pts64 = nyc_points(10_037, seed=9)    # 3 chunks, ragged tail
    ref = make_streamed_pip_join(idx, grid, polys=polys, chunk=4096)
    shj = make_sharded_streamed_pip_join(idx, grid, mesh,
                                         polys=polys, chunk=4096)
    z_ref, r_ref = ref(pts64)
    z_sh, r_sh = shj(pts64)
    assert np.array_equal(z_sh, z_ref)
    assert r_sh == r_ref
    assert np.array_equal(z_ref, pip_host_truth(pts64, polys))
    hist = zone_histogram(z_sh, len(polys))
    assert int(hist.sum()) == int(np.sum(z_sh >= 0))


def test_sharded_streamed_staged_rows_per_device(workload):
    """The sharded join counts the rows each device was handed, read off
    the staged arrays' own shards: every chunk splits evenly, padding
    included (4096 -> 1024 per device, the 1845-row tail -> 512)."""
    from mosaic_tpu.obs import metrics
    polys, grid, res, idx = workload
    mesh = _mesh4()
    shj = make_sharded_streamed_pip_join(idx, grid, mesh, polys=polys,
                                         chunk=4096)
    keys = [f"shard/staged_rows/pip_join/{d.platform}:{d.id}"
            for d in mesh.devices.flat]
    was = metrics.enabled
    metrics.enable()
    try:
        before = [metrics.counter_value(k) for k in keys]
        shj(nyc_points(10_037, seed=9))
        rows = [metrics.counter_value(k) - b for k, b in zip(keys, before)]
    finally:
        if not was:
            metrics.disable()
    assert rows == [1024 + 1024 + 512] * 4


def _skewed_cloud(polys, n=4096, frac=0.9, seed=21):
    """90% of points uniform inside zone 0's box, 10% just west of the
    workload bbox (unmatched, zone -1), cluster-first row order — the
    worst case for contiguous row-order sharding."""
    rng = np.random.default_rng(seed)
    x0, y0, x1, y1 = polys.bboxes()[0]
    n_hot = int(n * frac)
    hot = np.stack([rng.uniform(x0, x1, n_hot),
                    rng.uniform(y0, y1, n_hot)], -1)
    wx0 = float(polys.bboxes()[:, 0].min())   # workload west edge
    cold = np.stack([rng.uniform(wx0 - 0.2, wx0 - 0.05, n - n_hot),
                     rng.uniform(y0, y1, n - n_hot)], -1)
    return np.concatenate([hot, cold])


def test_skew_rebalance_cuts_shard_load(workload):
    """A deliberately skewed cloud: with arrival-order placement three
    shards hold only matched rows while the last holds every
    unmatched one; once the SkewRebalancer arms (refresh=2), the
    greedy placement spreads the hot zone's bins and the observed
    per-shard matched skew drops to ~1.0 (acceptance: <= 1.5) without
    changing a single output zone."""
    from mosaic_tpu.obs import metrics
    polys, grid, res, idx = workload
    pts64 = _skewed_cloud(polys)
    shj = make_sharded_streamed_pip_join(
        idx, grid, _mesh4(), polys=polys, chunk=len(pts64), refresh=2)
    ref = make_streamed_pip_join(idx, grid, polys=polys,
                                 chunk=len(pts64))
    z_ref, _ = ref(pts64)
    assert np.mean(z_ref >= 0) == pytest.approx(0.9, abs=0.02)
    was = metrics.enabled
    metrics.enable()
    try:
        z0, _ = shj(pts64)
        pre = metrics.gauge_value("shard/skew/pip_join")
        assert not shj.rebalancer.armed
        assert pre == pytest.approx(1.0 / 0.9, rel=0.02)
        z1, _ = shj(pts64)               # obs 2 of 2 -> rebalance
        assert shj.rebalancer.armed
        z2, _ = shj(pts64)               # first placed run
        post = metrics.gauge_value("shard/skew/pip_join")
    finally:
        if not was:
            metrics.disable()
    assert post <= 1.5
    assert post < pre
    assert shj.rebalancer.planned_skew() <= 1.5
    # rebalancing moves rows between devices, never changes results
    for z in (z0, z1, z2):
        assert np.array_equal(z, z_ref)


def test_greedy_bin_packing_balances_density():
    """Unit-level packing claim: 90% of density clustered in one
    corner quarter of the bin lattice loads contiguous-block
    placement ~2x over mean; the greedy desc-density pack lands
    within the 1.5 acceptance bound."""
    from mosaic_tpu.parallel.placement import SkewRebalancer
    rng = np.random.default_rng(5)
    n = 20_000
    n_hot = int(n * 0.9)
    hot = rng.uniform(0.0, 0.25, (n_hot, 2))      # corner quarter
    cold = rng.uniform(0.0, 1.0, (n - n_hot, 2))
    pts = np.concatenate([hot, cold])
    r = SkewRebalancer(4, refresh=1, nbins=8)
    r.observe(pts, np.ones(n, bool))              # arms immediately
    assert r.armed
    assert r.contiguous_skew() > 1.5
    assert r.planned_skew() <= 1.5
    assert r.planned_skew() < r.contiguous_skew()
    pref = r.preferred(pts)
    assert pref.shape == (n,) and set(np.unique(pref)) <= set(range(4))


def test_placement_slots_properties():
    from mosaic_tpu.parallel.placement import placement_slots
    # identity when no preference is known yet
    assert np.array_equal(placement_slots(None, 5, 4, 2), np.arange(5))
    # preferences honored up to capacity, overflow spills, all slots
    # unique and within the padded buffer
    pref = np.array([0, 0, 0, 0, 2, 2, 1])
    slots = placement_slots(pref, len(pref), 4, 2)
    assert len(np.unique(slots)) == len(pref)
    assert slots.min() >= 0 and slots.max() < 4 * 2
    shard = slots // 2
    assert np.bincount(shard, minlength=4).max() <= 2
    # rows preferring shard 2 fit under its capacity and stay there
    assert np.all(shard[4:6] == 2)
    with pytest.raises(ValueError):
        placement_slots(pref, 9, 4, 2)


def test_sharded_skew_refresh_conf_key(workload):
    """The sharded streamed join takes its placement cadence from
    mosaic.shard.skew.refresh: the rebalancer re-packs every K-th
    consumed chunk, not just once."""
    from mosaic_tpu import config as cfgmod
    polys, grid, res, idx = workload
    # conf-key plumbing
    cfg = cfgmod.apply_conf(cfgmod.MosaicConfig(),
                            "mosaic.shard.skew.refresh", "8")
    assert cfg.shard_skew_refresh == 8
    with pytest.raises(cfgmod.ConfigError):
        cfgmod.apply_conf(cfgmod.MosaicConfig(),
                          "mosaic.shard.skew.refresh", "0")
    old = cfgmod.default_config()
    try:
        cfgmod.set_default_config(
            dataclasses.replace(old, shard_skew_refresh=2))
        shj = make_sharded_streamed_pip_join(idx, grid, _mesh4(),
                                             polys=polys, chunk=1024)
        shj(nyc_points(5 * 1024, seed=13))          # 5 chunks
    finally:
        cfgmod.set_default_config(old)
    r = shj.rebalancer
    assert r.refresh == 2 and r.observations == 5
    # chunks 2 and 4 hit the cadence -> exactly 2 re-packs
    assert r.rebalances == 2


def test_coarse_res_continental_join_exact():
    """Continent-extent join at a COARSE resolution: the gap between
    the true gnomonic cell boundary (which assigns points) and the
    straight lon/lat chords the chips are clipped against is ~0.3 deg
    at res 2 — points inside that band must flag for the host pass
    instead of silently dropping (round-4: 7/20k points got zone -1
    while being degrees inside the polygon)."""
    import jax
    import mosaic_tpu as mos
    from mosaic_tpu.parallel.pip_join import (build_pip_index,
                                              host_recheck_fn,
                                              localize,
                                              make_pip_join_fn,
                                              pip_host_truth)
    grid = mos.get_index_system("H3")
    wide = mos.read_wkt(
        ["POLYGON ((-120 30, -70 30, -70 50, -120 50, -120 30))"])
    idx = build_pip_index(wide, 2, grid)
    rng = np.random.default_rng(0)
    pts = np.stack([rng.uniform(-121, -69, 20000),
                    rng.uniform(29, 51, 20000)], -1)
    fn = jax.jit(make_pip_join_fn(idx, grid))
    zone, unc = fn(localize(idx, pts))
    zone = host_recheck_fn(idx, wide)(pts, np.asarray(zone).copy(),
                                      np.asarray(unc))
    assert np.array_equal(zone, pip_host_truth(pts, wide))
    # the exact per-workload sagitta keeps the band a small fraction
    # at mid latitudes (~4% here: 2x0.022 deg band along every cell
    # edge of ~3.5 deg cells, plus the chip-edge eps flags)
    assert np.asarray(unc).mean() < 0.10

    # high-latitude box: the chord-vs-gnomonic deviation there is tens
    # of times larger (the sampled global bound used to miss it —
    # round-4 review found 2-37 unflagged wrong-zone points per 20k)
    polar = mos.read_wkt(
        ["POLYGON ((-30 55, 30 55, 30 75, -30 75, -30 55))"])
    idx2 = build_pip_index(polar, 2, grid)
    pts2 = np.stack([rng.uniform(-31, 31, 20000),
                     rng.uniform(54, 76, 20000)], -1)
    fn2 = jax.jit(make_pip_join_fn(idx2, grid))
    z2, u2 = fn2(localize(idx2, pts2))
    z2 = host_recheck_fn(idx2, polar)(pts2, np.asarray(z2).copy(),
                                      np.asarray(u2))
    assert np.array_equal(z2, pip_host_truth(pts2, polar))
