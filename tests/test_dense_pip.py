"""Dense lattice-window PIP index (parallel/pip_join.py).

The dense path replaces the sorted-table binary searches (29 serial
gathers/point measured at 56% of the TPU join) with one row gather a
point: the record table is keyed by lattice cell, and a cell's row
holds, in blocks of lanes, its border group's merged chip edges
(``ax | ay | bx | by``, E each), their zone slots, the group's Z zone
ids, its wide flag and the cell's entry code, the int lanes as int32
bit patterns.  A window whose cell-keyed table would pass
``CELL_ROWS_MAX_BYTES`` keeps one row per group behind an int32 entry
table (two gathers a point).  These tests pin both layouts against the
host recheck's f64 tables and each other, the exactness contract
against the float64 host oracle, and the equivalence with the
grid-agnostic sorted path.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mosaic_tpu.bench.workloads import build_workload, nyc_points
from mosaic_tpu.core.index.factory import get_index_system
from mosaic_tpu.core.geometry.wkt import read_wkt
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.obs import tracer
from mosaic_tpu.parallel import pip_join
from mosaic_tpu.parallel.pip_join import (DensePIPIndex, PIPIndex,
                                          build_dense_pip_index,
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
    assert R % 128 == 0 and R >= 5 * dense_idx.E + dense_idx.Z + 2
    assert dense_idx.layout == "cell_rows" and dense_idx.entry is None
    assert dense_idx.rec.shape[0] == dense_idx.W * dense_idx.H


def _is_border(entry):
    return (entry >= 0) & ((entry & pip_join.CORE_FLAG) == 0)


def _border_cells(idx):
    """Lattice cell of each border group, in group order."""
    entry = idx.aux["entry"]
    border = np.nonzero(_is_border(entry))[0]
    cell_of = np.empty(idx.groups, np.int64)
    cell_of[entry[border]] = border
    return cell_of


def _assert_rows_decode(idx, rows):
    """``rows[g]`` holds the f32 of group g's host-side chip edges
    (local frame), their zone slots, the group's zone ids, its wide
    flag and its index as code, with the pads the kernel relies on."""
    E, Z, aux = idx.E, idx.Z, idx.aux
    irows = rows.view(np.int32)
    gstart = aux["gstart"]
    G = len(gstart) - 1
    assert len(rows) == G == idx.groups and G > 0
    ox, oy = idx.origin
    for g in range(G):
        n = int(gstart[g + 1] - gstart[g])
        k = min(n, E)
        sl = slice(gstart[g], gstart[g] + k)
        a, b = aux["flat_a"][sl], aux["flat_b"][sl]
        want = [(a[:, 0] - ox), (a[:, 1] - oy), (b[:, 0] - ox),
                (b[:, 1] - oy)]
        for blk, w in enumerate(want):
            got = rows[g, blk * E:(blk + 1) * E]
            assert np.array_equal(got[:k], w.astype(np.float32)), (g, blk)
            assert np.all(got[k:] == np.float32(1e9)), (g, blk)
        zs = irows[g, 4 * E:5 * E]
        assert np.array_equal(zs[:k], aux["edge_zslot"][sl]), g
        assert np.all(zs[k:] == -1), g
        assert np.array_equal(irows[g, 5 * E:5 * E + Z], aux["gzones64"][g])
        assert irows[g, 5 * E + Z] == int(n > E), g
        assert irows[g, 5 * E + Z + 1] == g, g
        assert np.all(irows[g, 5 * E + Z + 2:] == 0), g


def test_dense_record_rows_decode_to_host_tables(dense_idx):
    """Each border cell's row decodes to its group's host tables."""
    rec = np.asarray(dense_idx.rec)
    _assert_rows_decode(dense_idx, rec[_border_cells(dense_idx)])


def test_dense_code_lane_is_entry_and_other_cells_hold_pads(dense_idx):
    """Every window cell's code lane is the host entry code; a cell
    that is not on a border holds the pads alone."""
    E, Z = dense_idx.E, dense_idx.Z
    entry = dense_idx.aux["entry"]
    rec = np.asarray(dense_idx.rec)
    irec = rec.view(np.int32)
    assert np.array_equal(irec[:, 5 * E + Z + 1], entry)
    pad = ~_is_border(entry)
    # empty and core cells are both there to check
    assert (entry == -1).any() and (entry != -1)[pad].any()
    assert np.all(rec[pad, :4 * E] == np.float32(1e9))
    assert np.all(irec[pad, 4 * E:5 * E + Z] == -1)
    assert np.all(irec[pad, 5 * E + Z] == 0)
    assert np.all(irec[pad, 5 * E + Z + 2:] == 0)


def test_group_rows_layout_over_the_budget_gives_the_same_join(
        workload, monkeypatch):
    """A window whose cell-keyed table passes CELL_ROWS_MAX_BYTES keeps
    one row per group behind the entry table; both layouts give the
    same bits, and the recheck the host truth."""
    polys, grid, res = workload
    chips = tessellate(polys, res, grid, keep_core_geom=False)
    tracer.reset()
    tracer.enable()
    try:
        cell = build_dense_pip_index(polys, res, grid, chips=chips)
        assert tracer.report()["counters"].get(
            "dense_layout/cell_rows") == 1
        table = cell.W * cell.H * cell.rec.shape[-1] * 4
        monkeypatch.setattr(pip_join, "CELL_ROWS_MAX_BYTES", table - 1)
        group = build_dense_pip_index(polys, res, grid, chips=chips)
        counters = tracer.report()["counters"]
    finally:
        tracer.disable()
        tracer.reset()
    assert counters.get("dense_layout/group_rows") == 1
    assert counters.get("dense_layout/cell_rows") == 1
    assert cell.layout == "cell_rows" and group.layout == "group_rows"
    assert np.array_equal(np.asarray(group.entry), cell.aux["entry"])
    _assert_rows_decode(group, np.asarray(group.rec))

    pts64 = nyc_points(50_000, seed=6)
    out = {}
    for idx in (cell, group):
        fn = jax.jit(make_pip_join_fn(idx, grid))
        zone, unc = fn(jnp.asarray(localize(idx, pts64)))
        out[idx.layout] = (np.asarray(zone), np.asarray(unc))
    (zc, uc), (zg, ug) = out["cell_rows"], out["group_rows"]
    assert np.array_equal(zc, zg) and np.array_equal(uc, ug)
    truth = pip_host_truth(pts64, polys)
    for idx in (cell, group):
        z, u = out[idx.layout]
        assert np.array_equal(host_recheck_fn(idx)(pts64, z, u), truth)


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
