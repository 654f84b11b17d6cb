"""The continuous profiling plane (``obs.profiler``).

Covers the acceptance surface of the profiling PR: host-sampler
lifecycle (no leaked threads), collapsed-stack correctness on a
synthetic workload, per-trace attribution with two interleaved SQL
queries, the kernel ledger joined against a warm streamed join, the
breach drill producing a flight bundle with a non-empty profile, the
shared dump cooldown, the recorder ring drop counter, speedscope
export shape, conf validation, ``device_trace``, and the dashboard's
``/api/profile`` + ``/profile`` routes.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import mosaic_tpu as mos
from mosaic_tpu import config as _config
from mosaic_tpu.obs import metrics, new_trace, recorder, tracer
from mosaic_tpu.obs.profiler import (DEFAULT_PROFILE_HZ, HostProfiler,
                                     KernelLedger, capture_snapshot,
                                     configure_profiler, ledger,
                                     maybe_device_capture, profiler,
                                     start_profiler, stop_profiler)


@pytest.fixture
def clean_obs():
    recorder.reset()
    recorder.enable()
    metrics.reset()
    metrics.enable()
    ledger.reset()
    yield
    stop_profiler()
    ledger.reset()
    metrics.disable()
    metrics.reset()
    recorder.reset()


@pytest.fixture
def clean_config():
    prev = _config.default_config()
    yield
    _config.set_default_config(prev)


@pytest.fixture
def session():
    ctx = mos.enable_mosaic("CUSTOM(-180,180,-90,90,2,360,180)")
    s = mos.SQLSession(ctx)
    s.create_table("pts", {"x": np.arange(100.0),
                           "y": np.arange(100.0) / 10.0})
    return s


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        assert r.status == 200
        return r.read().decode("utf-8")


# ----------------------------------------------------- lifecycle

def test_sampler_lifecycle_no_leaked_threads(clean_obs):
    before = threading.active_count()
    p = start_profiler(hz=200.0)
    assert p.alive and profiler() is p
    assert threading.active_count() == before + 1
    time.sleep(0.05)
    stop_profiler()
    assert profiler() is None and not p.alive
    assert threading.active_count() == before
    # restart replaces, never stacks
    p2 = start_profiler(hz=100.0)
    p3 = start_profiler(hz=100.0)
    assert not p2.alive and p3.alive
    assert threading.active_count() == before + 1
    stop_profiler()
    assert threading.active_count() == before
    # lifecycle transitions landed in the flight recorder
    assert len(recorder.events("profiler")) == 3


def test_hz_is_clamped_and_recorded(clean_obs):
    assert HostProfiler(hz=0.0001).hz == 0.5
    assert HostProfiler(hz=1e9).hz == 1000.0
    assert HostProfiler().hz == DEFAULT_PROFILE_HZ


def test_configure_profiler_conf_lifecycle(clean_obs, monkeypatch):
    monkeypatch.delenv("MOSAIC_TPU_PROFILE_HZ", raising=False)
    configure_profiler(50.0)
    p = profiler()
    assert p is not None and p.hz == 50.0
    configure_profiler(50.0)                  # no change -> same thread
    assert profiler() is p
    configure_profiler(0.0)
    assert profiler() is None
    # env pin: conf values are ignored while the env var is set
    monkeypatch.setenv("MOSAIC_TPU_PROFILE_HZ", "123")
    configure_profiler(75.0)
    assert profiler() is None


def test_profile_hz_conf_validation(clean_config):
    cfg = _config.default_config()
    cfg = _config.apply_conf(cfg, "mosaic.obs.profile.hz", "97")
    assert cfg.obs_profile_hz == 97.0
    cfg = _config.apply_conf(cfg, "mosaic.obs.dump.cooldown.ms", 1000)
    assert cfg.obs_dump_cooldown_ms == 1000.0
    with pytest.raises(_config.ConfigError):
        _config.apply_conf(cfg, "mosaic.obs.profile.hz", -5)
    with pytest.raises(_config.ConfigError):
        _config.apply_conf(cfg, "mosaic.obs.profile.hz", "fast")


# ------------------------------------------------ collapsed stacks

def _busy_until(stop):
    def inner_hot():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.002:
            pass
    while not stop.is_set():
        inner_hot()


def test_collapsed_stack_correctness_synthetic(clean_obs):
    """Manual sample() passes over a known two-frame workload: the
    collapsed output must contain the root->leaf chain in order."""
    p = HostProfiler(hz=100.0)                # never started: inline
    stop = threading.Event()
    t = threading.Thread(target=_busy_until, args=(stop,), daemon=True)
    t.start()
    try:
        for _ in range(30):
            p.sample()
            time.sleep(0.002)
    finally:
        stop.set()
        t.join()
    assert p.samples == 30
    rep = p.report()
    assert rep["distinct_stacks"] >= 1 and rep["truncated"] == 0
    busy = [s for s in rep["stacks"]
            if s["frames"][-1].endswith(":inner_hot")]
    assert busy, f"no inner_hot stack in {rep['stacks']}"
    # root-first ordering: the caller precedes the leaf on the line
    line = [l for l in p.collapsed().splitlines()
            if ":inner_hot" in l][0]
    frames, _, count = line.rpartition(" ")
    assert int(count) >= 1
    assert frames.index(":_busy_until") < frames.index(":inner_hot")


def test_collapsed_respects_bounds(clean_obs):
    p = HostProfiler(max_stacks=1, max_depth=2)
    stop = threading.Event()
    t = threading.Thread(target=_busy_until, args=(stop,), daemon=True)
    t.start()
    try:
        for _ in range(10):
            p.sample()
            time.sleep(0.002)
    finally:
        stop.set()
        t.join()
    rep = p.report()
    assert rep["distinct_stacks"] <= 1
    assert all(len(s["frames"]) <= 2 for s in rep["stacks"])
    p.reset()
    assert p.report()["distinct_stacks"] == 0 and p.samples == 0


def test_speedscope_schema(clean_obs):
    p = HostProfiler()
    stop = threading.Event()
    t = threading.Thread(target=_busy_until, args=(stop,), daemon=True)
    t.start()
    try:
        for _ in range(10):
            p.sample()
            time.sleep(0.002)
    finally:
        stop.set()
        t.join()
    ss = p.speedscope()
    assert ss["$schema"].startswith("https://www.speedscope.app")
    prof = ss["profiles"][0]
    assert prof["type"] == "sampled"
    n_frames = len(ss["shared"]["frames"])
    assert prof["samples"] and len(prof["samples"]) == \
        len(prof["weights"])
    assert all(0 <= ix < n_frames
               for row in prof["samples"] for ix in row)
    assert prof["endValue"] == sum(prof["weights"])
    json.dumps(ss)                            # fully serializable


# -------------------------------------------- per-trace attribution

def test_two_interleaved_queries_get_disjoint_profiles(
        clean_obs, session, fault_plan):
    """Two SQL queries running concurrently (held open by a fault-plan
    delay) must sample into distinct trace ids, each carrying its own
    stacks — the attribution contract."""
    fault_plan("site=sql.query,mode=delay,fails=2,delay_ms=400")
    p = HostProfiler()
    errs = []

    def q():
        try:
            session.sql("SELECT x FROM pts")
        except Exception as e:                # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=q, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.time() + 2.0
    while any(t.is_alive() for t in threads) and time.time() < deadline:
        p.sample()
        time.sleep(0.01)
    for t in threads:
        t.join(timeout=5)
    assert not errs
    rep = p.report()
    sql_traces = {tid: info for tid, info in rep["traces"].items()
                  if info["name"].startswith("sql:")}
    assert len(sql_traces) == 2, rep["traces"]
    assert all(info["samples"] > 0 for info in sql_traces.values())
    # stack keys are disjoint by construction: each trace's filtered
    # view is non-empty and its counts add up to that trace's rollup
    t1, t2 = sql_traces
    assert p.collapsed(t1) and p.collapsed(t2)
    for tid in (t1, t2):
        counts = sum(s["count"] for s in rep["stacks"]
                     if s["trace"] == tid)
        assert counts == sql_traces[tid]["samples"] > 0


# ------------------------------------------------- kernel ledger

def test_ledger_accumulates_and_bounds(clean_obs):
    led = KernelLedger(max_entries=2)
    led.observe("k/a", (64,), 0.5, rows=100)
    led.observe("k/a", (64,), 0.25, rows=100)
    led.observe("k/b", (128,), 0.25, rows=50)
    led.observe("k/c", (256,), 1.0, rows=10)  # over capacity: dropped
    rep = led.report()
    assert [e["name"] for e in rep["kernels"]] == ["k/a", "k/b"]
    assert rep["kernels"][0]["launches"] == 2
    assert rep["kernels"][0]["seconds"] == 0.75
    assert rep["kernels"][0]["rows_per_s"] == round(200 / 0.75)
    assert rep["dropped"] == 1
    assert led.seconds("k/a") == 0.75
    assert led.seconds() == 1.0
    led.record_cost("k/a", {"flops": 2e9, "label": "ignored"})
    e = led.report()["kernels"][0]
    assert e["cost"] == {"flops": 2e9}
    assert e["gflops_s"] == pytest.approx(2 * 2e9 / 0.75 / 1e9, rel=.01)


def test_ledger_joins_warm_streamed_join(clean_obs):
    """The flagship-shaped join feeds the ledger: one pip/streamed
    entry, one launch per chunk, and the observed seconds cover most
    of the measured wall time (the floor is loose for CI noise on a
    tiny workload)."""
    from mosaic_tpu import read_wkt
    from mosaic_tpu.core.index.custom import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.parallel.pip_join import (build_pip_index,
                                              make_streamed_pip_join)
    grid = CustomIndexSystem(GridConf(0, 16, 0, 16, 2, 1.0, 1.0))
    arr = read_wkt(
        ["POLYGON ((1.3 1.7, 6.8 2.1, 5.9 6.3, 2.2 5.8, 1.3 1.7))",
         "POLYGON ((8.5 1.5, 14.5 1.5, 14.5 6.5, 8.5 6.5, 8.5 1.5))"])
    idx = build_pip_index(arr, 1, grid,
                          chips=tessellate(arr, 1, grid))
    pts = np.random.default_rng(3).uniform(0, 16, (8192, 2))
    sjoin = make_streamed_pip_join(idx, grid, polys=arr, chunk=2048)
    sjoin(pts)                                # warm (compile)
    ledger.reset()
    t0 = time.perf_counter()
    sjoin(pts)
    wall = time.perf_counter() - t0
    rep = ledger.report()
    (e,) = [k for k in rep["kernels"] if k["name"] == "pip/streamed"]
    assert e["launches"] == 4                 # 8192 / 2048
    assert e["rows"] == 8192
    assert 0 < e["seconds"] <= wall * 1.05
    assert ledger.seconds("pip/streamed") >= 0.5 * wall
    # the jit cache seeded the entry name it registered under
    assert "pip/streamed" in {k["name"] for k in rep["kernels"]}


def test_jit_cache_registers_ledger_rows(clean_obs):
    from mosaic_tpu.perf.jit_cache import kernel_cache
    kernel_cache.get_or_build("test/ledger_seed", (7,), lambda: object)
    names = {k["name"] for k in ledger.report()["kernels"]}
    assert "test/ledger_seed" in names
    (e,) = [k for k in ledger.report()["kernels"]
            if k["name"] == "test/ledger_seed"]
    assert e["launches"] == 0                 # known, never observed


# --------------------------------------- triggered capture / bundles

def test_bundle_embeds_profile_snapshot(clean_obs):
    ledger.observe("k/x", (1,), 0.1)
    p = start_profiler(hz=200.0)
    stop = threading.Event()
    t = threading.Thread(target=_busy_until, args=(stop,), daemon=True)
    t.start()
    try:
        time.sleep(0.15)
    finally:
        stop.set()
        t.join()
    b = recorder.bundle(reason="test")
    assert b["dropped"] == 0
    prof = b["profile"]
    assert prof["collapsed"]                  # non-empty stacks
    assert prof["host"]["samples"] > 0
    assert [k["name"] for k in prof["ledger"]["kernels"]] == ["k/x"]
    stop_profiler()
    # snapshot stays well-formed with the sampler off
    snap = capture_snapshot()
    assert snap["collapsed"] == "" and snap["host"] == {}
    assert snap["ledger"]["kernels"]


def test_breach_drill_dump_contains_profile(
        clean_obs, clean_config, session, fault_plan, tmp_path,
        monkeypatch):
    """The acceptance drill: an SLO breach writes a flight bundle whose
    ``profile`` block carries non-empty collapsed stacks."""
    from mosaic_tpu.obs.slo import SLObjective, monitor
    from mosaic_tpu.obs.timeseries import timeseries
    monkeypatch.setenv("MOSAIC_TPU_DUMP_DIR", str(tmp_path))
    cfg = _config.apply_conf(_config.default_config(),
                             "mosaic.obs.slo.dump", True)
    _config.set_default_config(cfg)
    timeseries.reset()
    monitor.reset([SLObjective(
        name="sql_latency", kind="latency", series="sql/query_ms",
        threshold_ms=250.0, objective=0.95, min_points=1,
        windows=(60.0, 300.0))])
    start_profiler(hz=300.0)
    try:
        fault_plan("site=sql.query,mode=delay,fails=1,delay_ms=500")
        session.sql("SELECT x FROM pts")      # sampled while stalled
        trans = monitor.evaluate()
        assert [t["transition"] for t in trans] == ["breach"]
    finally:
        stop_profiler()
        monitor.reset()
        timeseries.reset()
    dumps = list(tmp_path.glob("*_slo_sql_latency.json"))
    assert len(dumps) == 1
    b = json.loads(dumps[0].read_text())
    assert b["profile"]["collapsed"]
    assert b["profile"]["host"]["samples"] > 0


def test_maybe_device_capture_disabled_is_none(clean_obs, clean_config):
    cfg = _config.apply_conf(_config.default_config(),
                             "mosaic.obs.profile.trace.ms", 0)
    _config.set_default_config(cfg)
    assert maybe_device_capture("test") is None


def test_device_trace_writes_logdir(tmp_path):
    import jax
    import jax.numpy as jnp
    from mosaic_tpu.obs import device_trace
    logdir = tmp_path / "trace"
    try:
        with device_trace(str(logdir)):
            jax.block_until_ready(jnp.arange(8.0) * 2.0)
    except Exception as e:
        pytest.skip(f"jax.profiler unavailable here: {e}")
    assert logdir.exists() and any(logdir.rglob("*"))


# ------------------------------------------- cooldown + drop counter

def test_dump_cooldown_suppresses_and_flushes(
        clean_obs, clean_config, tmp_path, monkeypatch):
    monkeypatch.setenv("MOSAIC_TPU_DUMP_DIR", str(tmp_path))
    assert recorder.dump_throttled(reason="slow_query") is not None
    # inside the 30 s default cooldown: held, counted, evented
    assert recorder.dump_throttled(reason="slow_query") is None
    assert recorder.dump_throttled(reason="slo_x") is None
    sup = recorder.events("dump_suppressed")
    assert [e["suppressed"] for e in sup] == [1, 2]
    assert len(list(tmp_path.glob("*.json"))) == 1
    # cooldown 0 disables the gate; the flush event reports the count
    cfg = _config.apply_conf(_config.default_config(),
                             "mosaic.obs.dump.cooldown.ms", 0)
    _config.set_default_config(cfg)
    assert recorder.dump_throttled(reason="slow_query") is not None
    (fl,) = recorder.events("dump_suppressed_flush")
    assert fl["suppressed"] == 2
    assert len(list(tmp_path.glob("*.json"))) == 2


def test_recorder_ring_drop_counter(clean_obs):
    recorder.reset(capacity=16)           # 16 is the ring's floor
    try:
        for i in range(20):
            recorder.record("tick", i=i)
        assert len(recorder.events("tick")) == 16
        assert recorder.dropped == 4
        assert recorder.bundle()["dropped"] == 4
        assert metrics.counter_value("obs/recorder_dropped") == 4
    finally:
        recorder.reset(capacity=4096)
    assert recorder.dropped == 0


# --------------------------------------------------- dashboard

def test_dashboard_profile_routes(clean_obs, session):
    from mosaic_tpu.obs import serve_dashboard
    ledger.observe("pip/streamed", (64,), 0.2, rows=640)
    start_profiler(hz=200.0)
    stop = threading.Event()
    t = threading.Thread(target=_busy_until, args=(stop,), daemon=True)
    t.start()
    handle = serve_dashboard(port=0)
    base = f"http://127.0.0.1:{handle.port}"
    try:
        time.sleep(0.1)
        prof = json.loads(_get(base + "/api/profile"))
        assert prof["running"] is True
        assert prof["host"]["samples"] > 0
        assert prof["collapsed"]
        names = [k["name"] for k in prof["ledger"]["kernels"]]
        assert "pip/streamed" in names
        # trace filter: an unknown trace id yields an empty profile
        empty = json.loads(_get(base + "/api/profile?trace=t0-nope"))
        assert empty["collapsed"] == "" and empty["host"]["stacks"] == []
        page = _get(base + "/profile")
        assert "Flame graph" in page and "/api/profile" in page
        root = _get(base + "/")
        assert "/profile" in root
    finally:
        stop.set()
        t.join()
        handle.close()
        stop_profiler()
