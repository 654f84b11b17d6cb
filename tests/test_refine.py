"""Adaptive PIP refinement: refined-vs-flat bit-parity, compile
accounting, planner pins, chaos, and the observability plumbing.

The refined join (parallel/pip_join.make_refined_pip_join) is a
strategy transform, never an answer transform: every test here asserts
results bit-for-bit against the flat single-level path and/or the
float64 host oracle (pip_host_truth).  The clean-index parity theorem
lives in pip_join._chips_clean's docstring; these tests are its
empirical side.
"""

import numpy as np
import pytest

from mosaic_tpu import config as _config
from mosaic_tpu.core.geometry.array import GeometryBuilder
from mosaic_tpu.core.index.h3.system import H3IndexSystem
from mosaic_tpu.obs import inflight, metrics
from mosaic_tpu.obs.recorder import recorder
from mosaic_tpu.parallel.pip_join import (build_pip_index,
                                          make_refined_pip_join,
                                          make_streamed_pip_join,
                                          pip_host_truth)
from mosaic_tpu.perf.jit_cache import kernel_cache


@pytest.fixture()
def conf():
    """Snapshot/restore the process config around each test."""
    prev = _config.default_config()
    yield
    _config.set_default_config(prev)


def _set(key, val):
    _config.set_default_config(_config.apply_conf(
        _config.default_config(), key, val))


def _cluster_polys(n=40, radius=0.004, spread=0.1, seed=0):
    """A tight cluster of small polygons sharing coarse grid cells —
    high per-cell chip duplication, the refinement target workload."""
    rng = np.random.default_rng(seed)
    b = GeometryBuilder()
    for cx, cy in rng.uniform(-spread, spread, size=(n, 2)):
        ang = np.linspace(0.0, 2.0 * np.pi, 8)[:-1]
        b.add_polygon(np.stack([cx + radius * np.cos(ang),
                                cy + radius * np.sin(ang)], 1), [])
    return b.finish()


def _points(kind, n, seed):
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        return rng.uniform(-0.15, 0.15, size=(n, 2))
    if kind == "skewed":
        return np.concatenate([
            rng.uniform(-0.12, 0.12, size=(n * 3 // 4, 2)),
            rng.uniform(-2.0, 2.0, size=(n - n * 3 // 4, 2))])
    if kind == "clustered":
        c = rng.uniform(-0.1, 0.1, size=(8, 2))
        return (c[rng.integers(0, 8, n)]
                + rng.normal(0.0, 0.01, size=(n, 2)))
    if kind == "empty_cells":
        # every point far outside the polygon cluster: the probe sees
        # zero candidate pairs, the dense set is empty
        return rng.uniform(50.0, 60.0, size=(n, 2))
    raise AssertionError(kind)


GRID = H3IndexSystem()
RES = 5


def _flat_reference(polys, pts):
    idx = build_pip_index(polys, RES, GRID, dense="never")
    flat = make_streamed_pip_join(idx, GRID, polys=polys, chunk=4096)
    z, _ = flat(pts)
    return np.asarray(z)


@pytest.mark.parametrize("kind", ["uniform", "skewed", "clustered",
                                  "empty_cells"])
def test_refined_vs_flat_bit_parity(conf, kind):
    """Fuzz the refined path against the flat path AND the float64
    host oracle across point distributions — including the empty-dense
    case where the probe finds nothing to refine."""
    _set("mosaic.planner.force.refine", "refined")
    _set("mosaic.join.refine.dup.threshold", "2")
    polys = _cluster_polys(seed=3)
    pts = _points(kind, 12_000, seed=11)
    run = make_refined_pip_join(polys, GRID, RES, chunk=4096)
    z_ref, _ = run(pts)
    z_flat = _flat_reference(polys, pts)
    assert np.array_equal(np.asarray(z_ref), z_flat)
    assert np.array_equal(np.asarray(z_ref), pip_host_truth(pts, polys))
    assert run.last_decision is not None
    assert run.stats["strategy"] in ("refined", "flat")
    if kind == "skewed":
        assert run.stats["strategy"] == "refined"
        assert run.stats["levels"] == [RES, RES + 1]
        assert run.stats["refined_points"] > 0


def test_one_compile_per_level_and_bucket(conf):
    """A warm refined process compiles nothing new: kernels are cached
    per (level, pow2 bucket), so repeat calls — and a second join over
    the same shapes — reuse every compiled executable."""
    _set("mosaic.planner.force.refine", "refined")
    _set("mosaic.join.refine.dup.threshold", "2")
    polys = _cluster_polys(seed=5)
    pts = _points("skewed", 10_000, seed=21)
    run = make_refined_pip_join(polys, GRID, RES, chunk=4096)
    z0, _ = run(pts)                # cold: probe + compiles
    s0 = kernel_cache.stats()
    for _ in range(3):
        z1, _ = run(pts)
        assert np.array_equal(np.asarray(z0), np.asarray(z1))
    s1 = kernel_cache.stats()
    assert s1["misses"] == s0["misses"], \
        "warm refined reps must not compile"
    assert s1["hits"] > s0["hits"]


def test_refine_disabled_kill_switch(conf):
    """mosaic.join.refine.enabled=false forces the flat path — it
    beats any pin — and the answer is unchanged."""
    _set("mosaic.join.refine.enabled", "false")
    _set("mosaic.planner.force.refine", "refined")   # loses to the switch
    polys = _cluster_polys(seed=7)
    pts = _points("skewed", 8_000, seed=31)
    run = make_refined_pip_join(polys, GRID, RES, chunk=4096)
    z, _ = run(pts)
    d = run.last_decision
    assert d.strategy == "flat" and d.forced
    assert run.stats["strategy"] == "flat"
    assert np.array_equal(np.asarray(z), _flat_reference(polys, pts))


def test_refine_forced_pin_parity(conf):
    """Pinning refined vs flat through mosaic.planner.force.refine
    yields bit-identical answers (the planner only picks speed)."""
    _set("mosaic.join.refine.dup.threshold", "2")
    polys = _cluster_polys(seed=9)
    pts = _points("skewed", 8_000, seed=41)
    run = make_refined_pip_join(polys, GRID, RES, chunk=4096)
    _set("mosaic.planner.force.refine", "refined")
    z_ref, _ = run(pts)
    assert run.last_decision.forced
    assert run.stats["strategy"] == "refined"
    _set("mosaic.planner.force.refine", "flat")
    z_flat, _ = run(pts)
    assert run.last_decision.forced
    assert run.stats["strategy"] == "flat"
    assert np.array_equal(np.asarray(z_ref), np.asarray(z_flat))


def test_refine_chaos_bailout(conf, fault_plan):
    """An injected fault at site=join.refine mid-refined-run falls
    back to the flat path transparently: correct answer, a
    refine_bailout flight-recorder event, and the bailout counter."""
    _set("mosaic.planner.force.refine", "refined")
    _set("mosaic.join.refine.dup.threshold", "2")
    polys = _cluster_polys(seed=13)
    pts = _points("skewed", 8_000, seed=51)
    run = make_refined_pip_join(polys, GRID, RES, chunk=4096)
    recorder.reset()
    recorder.enable()
    metrics.enable()
    c0 = metrics.counter_value("pip_join/refine_bailouts")
    try:
        fault_plan("seed=17;site=join.refine,fails=1")
        z, _ = run(pts)
    finally:
        recorder.disable()
    assert np.array_equal(np.asarray(z), pip_host_truth(pts, polys))
    assert run.stats["strategy"] == "flat"
    evs = recorder.events("refine_bailout")
    assert len(evs) == 1 and evs[0]["error"].startswith("Injected")
    assert metrics.counter_value("pip_join/refine_bailouts") == c0 + 1


def test_refine_ticket_cost_and_strategy(conf):
    """A refined join under a registered query ticket lands its cell
    counters in the inflight cost vector and its decision label in the
    strategies map (the history/mosaicstat strategies feed)."""
    from mosaic_tpu.obs.context import root_trace
    _set("mosaic.planner.force.refine", "refined")
    _set("mosaic.join.refine.dup.threshold", "2")
    polys = _cluster_polys(seed=15)
    pts = _points("skewed", 8_000, seed=61)
    run = make_refined_pip_join(polys, GRID, RES, chunk=4096)
    inflight.enabled = True
    with root_trace("q"):
        t = inflight.register("test refine", principal="t")
        try:
            run(pts)
            cost = t.cost()
            assert cost["cells_refined"] > 0
            assert cost["cells_flat"] >= 0
            assert "refine" in t.strategies
            assert t.strategies["refine"].startswith("refined")
            assert t.refine_ops and "L5+1" in t.refine_ops[0][1]
        finally:
            inflight.finish(t)


def test_explain_analyze_refine_column(conf):
    """EXPLAIN shows a static '-' refine column; EXPLAIN ANALYZE
    surfaces the per-operator refinement summaries noted on the
    query's live ticket."""
    from mosaic_tpu.functions.context import MosaicContext
    from mosaic_tpu.obs.inflight import note_refine, ticket_observer
    from mosaic_tpu.sql import SQLSession
    try:
        mc = MosaicContext.context()
    except RuntimeError:
        mc = MosaicContext.build("CUSTOM(-180,180,-90,90,2,360,180)")
    s = SQLSession(mc)
    rng = np.random.default_rng(8)
    s.create_table("rpts", {"cell": rng.integers(0, 20, 500),
                            "v": rng.normal(size=500)})
    s.create_table("rz", {"index_id": np.arange(20)})
    q = ("SELECT count(*) FROM rpts JOIN rz "
         "ON rpts.cell = rz.index_id")
    plan = s.sql("EXPLAIN " + q).to_dict()
    assert all(r == "-" for r in plan["refine"])

    def obs(tkt):
        note_refine({"cells_refined": 2, "cells_flat": 3},
                    summary="L5+1: 2 refined / 3 flat cells")
    with ticket_observer(obs):
        out = s.sql("EXPLAIN ANALYZE " + q).to_dict()
    assert any("L5+1" in r for r in out["refine"])
    s.drop_table("rpts")
    s.drop_table("rz")
