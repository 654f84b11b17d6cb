"""The streamed join's stages on the profiler's clock.

Program spans (``obs.tracer``) enter a ``jax.profiler.TraceAnnotation``
named ``mosaic/<span>`` while a profiler session records, so the
pipeline's put / dispatch / wait / fetch / consume stages land in the
device trace; with the metrics registry on, ``stream()`` also counts
its head, tail, staging and wait seconds.  The join kernels carry
stable module names and named scopes.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.bench.workloads import build_workload, nyc_points
from mosaic_tpu.obs import device_trace, metrics, tracer
from mosaic_tpu.parallel.pip_join import (DensePIPIndex, build_pip_index,
                                          make_pip_join_fn,
                                          make_sharded_streamed_pip_join,
                                          make_streamed_pip_join)

N = 1 << 14
CHUNK = 1 << 12
STAGES = ("put", "dispatch", "wait", "fetch", "consume")
COUNTERS = ("pipeline/head_s", "pipeline/tail_s", "pipeline/put_s",
            "pipeline/wait_s")


@pytest.fixture(scope="module")
def dense():
    polys, grid, res = build_workload(n_side=5, grid_name="H3",
                                      zones="taxi")
    idx = build_pip_index(polys, res, grid)
    assert isinstance(idx, DensePIPIndex)
    run = make_streamed_pip_join(idx, grid, polys=polys, chunk=CHUNK)
    pts = nyc_points(N, seed=3)
    run(pts)                                   # compile outside the tests
    return idx, grid, run, pts


@pytest.fixture
def quiet_obs():
    """Tracer and registry off, as a process starts."""
    was = metrics.enabled
    tracer.disable()
    tracer.reset()
    yield
    tracer.reset()
    if was:
        metrics.enable()


def _host_events(logdir):
    """``[(line index, name, start_ns, end_ns)]`` of the ``mosaic/``
    host events in the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                out.extend((k, e.name, e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith("mosaic/"))
    return out


def test_recording_profiler_sees_pipeline_spans(dense, quiet_obs, tmp_path):
    _, _, run, pts = dense
    with device_trace(str(tmp_path), host_tracer_level=1):
        run(pts)
    events = _host_events(tmp_path)
    names = {n for _, n, _, _ in events}
    assert {"mosaic/pip_join/streamed"} | {
        f"mosaic/pipeline/{s}" for s in STAGES} <= names
    lines = {s: {k for k, n, _, _ in events if n == f"mosaic/pipeline/{s}"}
             for s in STAGES}
    # put and dispatch on the dispatch loop's thread, fetch and consume
    # on the worker's
    assert lines["put"] == lines["dispatch"]
    assert lines["fetch"] == lines["consume"]
    assert not lines["put"] & lines["fetch"]
    # one put, dispatch, fetch and consume a chunk
    for s in ("put", "dispatch", "fetch", "consume"):
        assert sum(n == f"mosaic/pipeline/{s}" for _, n, _, _ in events) \
            == N // CHUNK
    # every stage falls inside the join's own span, on one clock
    ((_, _, j0, j1),) = [e for e in events
                         if e[1] == "mosaic/pip_join/streamed"]
    assert all(j0 <= s0 and s1 <= j1 for _, n, s0, s1 in events
               if n.startswith("mosaic/pipeline/"))


def test_no_profiler_and_tracer_off_records_nothing(dense, quiet_obs):
    _, _, run, pts = dense
    run(pts)
    assert tracer.events() == []
    assert tracer.report()["spans"] == {}


def _pipeline_counters():
    return {k: v for k, v in metrics.report()["counters"].items()
            if k.startswith("pipeline/")}


def test_stage_counters_split_the_call(dense, quiet_obs):
    import time
    _, _, run, pts = dense
    metrics.enable()
    try:
        before = _pipeline_counters()
        t0 = time.perf_counter()
        run(pts)
        wall = time.perf_counter() - t0
        moved = {k: v - before.get(k, 0.0)
                 for k, v in _pipeline_counters().items()}
        counters = metrics.report()["counters"]
    finally:
        metrics.disable()
    assert all(moved[c] > 0 for c in COUNTERS), moved
    assert moved["pipeline/head_s"] + moved["pipeline/tail_s"] <= wall
    assert moved["pipeline/put_s"] < wall and moved["pipeline/wait_s"] < wall
    # the per-call counters nothing read are gone
    for gone in ("pip_join/streamed_points", "pip_join/streamed_chunks",
                 "pipeline/d2h_bytes"):
        assert gone not in counters


def test_stage_counters_stay_still_with_metrics_off(dense, quiet_obs):
    _, _, run, pts = dense
    before = _pipeline_counters()
    run(pts)
    assert _pipeline_counters() == before


def test_dense_kernel_has_a_name_and_scopes(dense):
    idx, grid, _, _ = dense
    # the program a call runs: the index's tables are its arguments
    lowered = make_pip_join_fn(idx, grid).lower(
        jax.ShapeDtypeStruct((CHUNK, 2), jnp.float32))
    text = lowered.as_text(debug_info=True)
    assert "module @jit_pip_dense_join" in text
    for scope in ("project", "cell_lookup", "edge_pool", "zone_parity",
                  "flags"):
        assert f"/{scope}/" in text, scope


def test_sorted_kernel_has_a_name():
    polys, grid, res = build_workload(n_side=4, res_cells=32)
    idx = build_pip_index(polys, res, grid)
    lowered = jax.jit(make_pip_join_fn(idx, grid)).lower(
        jax.ShapeDtypeStruct((256, 2), jnp.float32))
    assert "module @jit_pip_sorted_join" in lowered.as_text()


def test_sharded_streamed_join_counts_its_recheck(quiet_obs):
    polys, grid, res = build_workload(n_side=6, res_cells=64)
    idx = build_pip_index(polys, res, grid)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    run = make_sharded_streamed_pip_join(idx, grid, mesh, polys=polys,
                                         chunk=4096)
    pts = nyc_points(10_037, seed=9)
    metrics.enable()
    try:
        before = metrics.counter_value("pip_join/recheck_s")
        run(pts)
        moved = metrics.counter_value("pip_join/recheck_s") - before
        counters = metrics.report()["counters"]
    finally:
        metrics.disable()
    assert moved > 0
    assert "pip_join/sharded_points" not in counters
    assert "pip_join/sharded_chunks" not in counters
