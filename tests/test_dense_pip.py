"""Dense lattice-window PIP index (parallel/pip_join.py).

The dense path replaces the sorted-table binary searches (29 serial
gathers/point measured at 56% of the TPU join) with two row gathers a
point: the window's entry table, then one lane-dense record row of the
point's border cell.  A row holds, in blocks of lanes, the cell's merged
chip edges (``ax | ay | bx | by``, E each), their zone slots, the cell's
Z zone ids and its wide flag, the int lanes as int32 bit patterns.
These tests pin that layout against the host recheck's f64 tables, the
exactness contract against the float64 host oracle, and the equivalence
with the grid-agnostic sorted path.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mosaic_tpu.bench.workloads import build_workload, nyc_points
from mosaic_tpu.core.index.factory import get_index_system
from mosaic_tpu.core.geometry.wkt import read_wkt
from mosaic_tpu.parallel.pip_join import (DensePIPIndex, PIPIndex,
                                          build_pip_index, host_recheck,
                                          host_recheck_fn, localize,
                                          make_pip_join_fn, pip_host_truth)


@pytest.fixture(scope="module")
def workload():
    polys, grid, res = build_workload(n_side=5, grid_name="H3",
                                      zones="taxi")
    return polys, grid, res


@pytest.fixture(scope="module")
def dense_idx(workload):
    polys, grid, res = workload
    idx = build_pip_index(polys, res, grid)
    assert isinstance(idx, DensePIPIndex)
    return idx


def test_dense_selected_for_city_h3(dense_idx):
    assert dense_idx.W > 10 and dense_idx.H > 10
    R = dense_idx.rec.shape[-1]
    assert R % 128 == 0 and R >= 5 * dense_idx.E + dense_idx.Z + 1


def test_dense_record_rows_decode_to_host_tables(dense_idx):
    """Every group's record row holds the f32 of its host-side chip
    edges (local frame), their zone slots, the group's zone ids and its
    wide flag, with the pads the kernel relies on."""
    E, Z, aux = dense_idx.E, dense_idx.Z, dense_idx.aux
    rec = np.asarray(dense_idx.rec)
    irec = rec.view(np.int32)
    gstart = aux["gstart"]
    G = len(gstart) - 1
    assert rec.shape[0] == G and G > 0
    ox, oy = dense_idx.origin
    for g in range(G):
        n = int(gstart[g + 1] - gstart[g])
        k = min(n, E)
        sl = slice(gstart[g], gstart[g] + k)
        a, b = aux["flat_a"][sl], aux["flat_b"][sl]
        want = [(a[:, 0] - ox), (a[:, 1] - oy), (b[:, 0] - ox),
                (b[:, 1] - oy)]
        for blk, w in enumerate(want):
            got = rec[g, blk * E:(blk + 1) * E]
            assert np.array_equal(got[:k], w.astype(np.float32)), (g, blk)
            assert np.all(got[k:] == np.float32(1e9)), (g, blk)
        zs = irec[g, 4 * E:5 * E]
        assert np.array_equal(zs[:k], aux["edge_zslot"][sl]), g
        assert np.all(zs[k:] == -1), g
        assert np.array_equal(irec[g, 5 * E:5 * E + Z], aux["gzones64"][g])
        assert irec[g, 5 * E + Z] == int(n > E), g
        assert np.all(irec[g, 5 * E + Z + 1:] == 0), g


def test_dense_join_matches_host_oracle(workload, dense_idx, rng):
    polys, grid, res = workload
    fn = jax.jit(make_pip_join_fn(dense_idx, grid))
    pts64 = nyc_points(20_000, seed=3)
    zone, unc = fn(jnp.asarray(localize(dense_idx, pts64)))
    zone = np.asarray(zone)
    unc = np.asarray(unc)
    truth = pip_host_truth(pts64, polys)
    # contract: every device/f64 disagreement is flagged
    assert not np.any((zone != truth) & ~unc)
    # and the recheck resolves all flags exactly
    final = host_recheck_fn(dense_idx)(pts64, zone, unc)
    assert np.array_equal(final, truth)
    # the flag set stays a sliver
    assert unc.mean() < 5e-3


def test_dense_equals_sorted_path(workload, dense_idx):
    polys, grid, res = workload
    sorted_idx = build_pip_index(polys, res, grid, dense="never")
    assert isinstance(sorted_idx, PIPIndex)
    pts64 = nyc_points(10_000, seed=4)
    fd = jax.jit(make_pip_join_fn(dense_idx, grid))
    fs = jax.jit(make_pip_join_fn(sorted_idx, grid))
    zd, ud = fd(jnp.asarray(localize(dense_idx, pts64)))
    zs, us = fs(jnp.asarray(localize(sorted_idx, pts64)))
    zd = host_recheck_fn(dense_idx)(pts64, np.asarray(zd), np.asarray(ud))
    zs = host_recheck(pts64, np.asarray(zs), np.asarray(us), polys)
    assert np.array_equal(zd, zs)


def test_vectorized_recheck_equals_polygon_loop(workload, dense_idx):
    """host_recheck_fn (chip CSR, vectorized) == the per-polygon loop."""
    polys, grid, res = workload
    fn = jax.jit(make_pip_join_fn(dense_idx, grid))
    pts64 = nyc_points(30_000, seed=5)
    zone, unc = fn(jnp.asarray(localize(dense_idx, pts64)))
    zone = np.asarray(zone)
    # recheck EVERYTHING through both paths (not just the flagged set)
    all_on = np.ones(len(pts64), bool)
    via_chips = host_recheck_fn(dense_idx)(pts64, zone.copy(), all_on)
    via_polys = host_recheck(pts64, zone.copy(), all_on, polys)
    assert np.array_equal(via_chips, via_polys)


def test_fallback_out_of_window():
    """Points far outside the window resolve to -1, certainly."""
    polys, grid, res = build_workload(n_side=4, grid_name="H3",
                                      zones="quad")
    idx = build_pip_index(polys, res, grid)
    if not isinstance(idx, DensePIPIndex):
        pytest.skip("dense path not selected")
    fn = jax.jit(make_pip_join_fn(idx, grid))
    far = np.array([[-73.0, 41.5], [-75.3, 40.0], [-74.0, 41.4]])
    zone, unc = fn(jnp.asarray(localize(idx, far)))
    assert np.all(np.asarray(zone) == -1)


def test_multiface_falls_back_to_sorted():
    """A polygon spanning icosahedron faces can't use the dense window."""
    wkt = ["POLYGON((-30 20, 20 20, 20 60, -30 60, -30 20))"]
    polys = read_wkt(wkt)
    grid = get_index_system("H3")
    idx = build_pip_index(polys, 2, grid)
    assert isinstance(idx, PIPIndex)


def test_dense_recheck_exact_in_cell_edge_sagitta_band(workload,
                                                        dense_idx):
    """Points between a cell's straight lon/lat chord (which its chips
    are clipped against) and its true gnomonic edge (which assigns
    them) sit in their H3 cell yet outside its chips: the recheck must
    answer them from the original polygons, not the chips."""
    polys, grid, res = workload
    cells = np.unique(grid.point_to_cell(nyc_points(4_000, seed=5), res))
    verts, counts = grid.cell_boundary(cells)
    sag = grid.cells_edge_sagitta_deg(cells)
    pts = []
    for v, k in zip(verts, counts):
        a = v[:k]
        b = np.roll(a, -1, axis=0)
        d = b - a
        normal = np.stack([d[:, 1], -d[:, 0]], -1)
        normal /= np.linalg.norm(normal, axis=1, keepdims=True)
        for t in np.linspace(-2.0, 2.0, 9) * sag:
            pts.append(0.5 * (a + b) + t * normal)
    pts64 = np.concatenate(pts)
    fn = jax.jit(make_pip_join_fn(dense_idx, grid))
    zone, unc = fn(jnp.asarray(localize(dense_idx, pts64)))
    final = host_recheck_fn(dense_idx)(pts64, np.asarray(zone),
                                       np.asarray(unc))
    truth = pip_host_truth(pts64, polys)
    assert np.array_equal(final, truth), int(np.sum(final != truth))
