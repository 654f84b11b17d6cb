"""Telemetry time-series store: rollup math, bounded memory,
snapshot/restore, and the background sampler lifecycle.

The rollup tests compare windowed reads against brute force over the
original point stream — levels strictly partition time, so a windowed
count pins down exactly which suffix of the stream is in view and
count/sum/min/max must match that suffix exactly (quantiles are exact
only while the window sits inside the raw ring).
"""

import json
import time

import pytest

import importlib

from mosaic_tpu.obs import metrics

# NB: the package re-exports the store singleton under the module's
# own name, so attribute-style module imports resolve to the store —
# go through sys.modules for the module itself.
ts_mod = importlib.import_module("mosaic_tpu.obs.timeseries")
from mosaic_tpu.obs.timeseries import (BUCKET_CAP, MAX_SERIES, RAW_CAP,
                                       Sampler, Series, TimeSeriesStore,
                                       configure_sampler, sampler,
                                       start_sampler, stop_sampler)


@pytest.fixture
def clean_sampler():
    """No sampler running before/after; conf latch cleared."""
    stop_sampler()
    prev_conf = ts_mod._conf_ms
    ts_mod._conf_ms = None
    yield
    stop_sampler()
    ts_mod._conf_ms = prev_conf


def make_series(values, t0=1000.0, dt=1.0):
    s = Series("t")
    for i, v in enumerate(values):
        s.append(t0 + i * dt, float(v))
    return s


# --------------------------------------------------- rollup vs brute

def test_rollups_match_bruteforce_suffix():
    n = 12_345
    vals = [((i * 37) % 1001) / 7.0 for i in range(n)]
    s = make_series(vals)
    now = 1000.0 + n            # just past the newest point
    # a spread of windows: raw-only, straddling mid, straddling
    # coarse, and all-history
    for seconds in (10, RAW_CAP // 2, RAW_CAP + 500, 4000, n + 10):
        st = s.window_stats(seconds, now=now)
        k = int(st["count"])
        assert 0 < k <= n
        suffix = vals[-k:]      # partitioned levels => a pure suffix
        assert st["sum"] == pytest.approx(sum(suffix))
        assert st["min"] == min(suffix)
        assert st["max"] == max(suffix)
        assert st["mean"] == pytest.approx(sum(suffix) / k)
    # the full-history window sees every point ever appended
    st = s.window_stats(n + 10, now=now)
    assert st["count"] == n == len(s)
    assert st["sum"] == pytest.approx(sum(vals))


def test_window_count_covers_at_least_the_cutoff():
    # a bucket straddling the cutoff is included whole: the window
    # never under-reports, and over-reports by less than one coarse
    # bucket (FOLD*FOLD points)
    n = 9_000
    s = make_series(range(n))
    now = 1000.0 + n
    for seconds in (700, 2500, 6000):
        exact = sum(1 for i in range(n)
                    if 1000.0 + i >= now - seconds)
        k = s.window_stats(seconds, now=now)["count"]
        assert exact <= k <= exact + ts_mod.FOLD * ts_mod.FOLD


def test_quantiles_exact_inside_raw_ring():
    s = make_series(range(1, 101))          # 1..100, all raw
    now = 1000.0 + 100
    assert s.quantile_over_window(50, 1000, now=now) == 50
    assert s.quantile_over_window(99, 1000, now=now) == 99
    assert s.quantile_over_window(100, 1000, now=now) == 100


def test_rate_is_exact_across_rollups():
    # counter series value = 3*i at 1 Hz => rate 3/s over any window,
    # including windows reaching into folded history
    n = 5_000
    s = make_series([3 * i for i in range(n)])
    now = 1000.0 + n
    for seconds in (50, 1000, n + 10):
        assert s.rate(seconds, now=now) == pytest.approx(3.0)
    assert Series("empty").rate(60) == 0.0


def test_fraction_over_exact_on_raw():
    s = make_series([1, 5, 9, 2, 8])
    bad, total = s.fraction_over(4.0, 1000, now=1000.0 + 5)
    assert (bad, total) == (3, 5)


# ------------------------------------------------------------ bounds

def test_series_memory_is_bounded():
    n = 200_000
    s = make_series([0.0] * 0)
    for i in range(n):
        s.append(1000.0 + i, float(i % 17))
    assert len(s.raw) <= RAW_CAP
    assert len(s.mid) <= BUCKET_CAP
    assert len(s.coarse) <= BUCKET_CAP
    assert s.dropped > 0                     # far tail really dropped
    # everything retained + everything dropped == everything appended
    assert len(s) + s.dropped * ts_mod.FOLD * ts_mod.FOLD == n


def test_store_caps_series_names():
    store = TimeSeriesStore()
    for i in range(MAX_SERIES + 10):
        store.record(f"s/{i}", 1.0, ts=1000.0)
    assert len(store) == MAX_SERIES
    assert store.names_dropped == 10
    # existing series still record fine
    store.record("s/0", 2.0, ts=1001.0)
    assert store.window_stats("s/0", 10, now=1001.0)["count"] == 2


def test_store_reads_absent_series_degrade():
    store = TimeSeriesStore()
    assert store.window_stats("nope", 60)["count"] == 0
    assert store.rate("nope", 60) == 0.0
    assert store.quantile_over_window("nope", 99, 60) == 0.0
    assert store.fraction_over("nope", 1.0, 60) == (0, 0)


# ------------------------------------------------- snapshot / restore

def test_snapshot_restore_roundtrip_through_json():
    store = TimeSeriesStore()
    for i in range(7_000):                   # deep enough to fold
        store.record("a", float(i % 13), ts=1000.0 + i)
    store.record("b", 42.0, ts=1000.0)
    snap = json.loads(json.dumps(store.snapshot()))   # wire round-trip
    other = TimeSeriesStore()
    assert other.restore(snap) == 2
    now = 1000.0 + 7_000
    for seconds in (100, 3000, 8000):
        assert other.window_stats("a", seconds, now=now) == \
            store.window_stats("a", seconds, now=now)
    assert other.rate("a", 8000, now=now) == \
        store.rate("a", 8000, now=now)
    assert other.window_stats("b", 10_000, now=now)["max"] == 42.0


def test_restore_rejects_unknown_version():
    store = TimeSeriesStore()
    assert store.restore({"version": 99, "series": {"x": {}}}) == 0
    assert store.restore("garbage") == 0
    assert len(store) == 0


# ----------------------------------------------------------- sampler

def test_sampler_tick_snapshots_registry():
    store = TimeSeriesStore()
    metrics.enable()
    try:
        metrics.count("tick/c", 5)
        metrics.gauge("tick/g", 2.5)
        metrics.observe("tick/h", 10.0)
        s = Sampler(50.0, store)
        s.tick(now=1000.0)
        s.tick(now=1001.0)
        assert s.ticks == 2
        assert store.window_stats("tick/c", 60, now=1001.0)["max"] == 5
        assert store.window_stats("tick/g", 60, now=1001.0)["max"] == 2.5
        assert store.window_stats("tick/h:count", 60,
                                  now=1001.0)["max"] == 1
        assert store.window_stats("tick/h:sum", 60,
                                  now=1001.0)["max"] == 10.0
    finally:
        metrics.disable()
        metrics.reset()


def test_sampler_start_stop_lifecycle(clean_sampler):
    store = TimeSeriesStore()
    metrics.enable()
    try:
        metrics.count("life/c")
        h = start_sampler(20.0, store)
        assert sampler() is h and h.alive
        deadline = time.time() + 5.0
        while h.ticks == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert h.ticks > 0, "sampler thread never ticked"
        stop_sampler()
        assert sampler() is None and not h.alive
        assert store.series("life/c") is not None
    finally:
        metrics.disable()
        metrics.reset()


def test_configure_sampler_conf_lifecycle(clean_sampler):
    configure_sampler(30.0)
    assert sampler() is not None
    assert sampler().interval_ms == 30.0
    configure_sampler(30.0)                  # same value: no restart
    first = sampler()
    configure_sampler(30.0)
    assert sampler() is first
    configure_sampler(0.0)                   # conf stops what conf started
    assert sampler() is None


def test_configure_sampler_keeps_programmatic_sampler(clean_sampler):
    h = start_sampler(25.0)
    configure_sampler(0.0)   # a SET with cadence 0 while conf never
    assert sampler() is h    # started one must not kill this sampler
    stop_sampler()


def test_env_var_pins_cadence(clean_sampler, monkeypatch):
    monkeypatch.setenv("MOSAIC_TPU_OBS_SAMPLE_MS", "250")
    configure_sampler(30.0)                  # ignored while pinned
    assert sampler() is None
