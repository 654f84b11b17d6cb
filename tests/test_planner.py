"""Cost-based adaptive planner tests.

The planner (sql/planner.py) is a pure strategy transform: it may only
change WHERE/HOW an operator runs, never what it returns.  These tests
pin (a) that invariant end-to-end across forced strategies, (b) the
learned-coefficient feedback loop (decisions flip when the observed
costs flip; estimate error converges after repeated runs), (c) the
persistence contract (warm start, versioned schema, corrupt-file
degrade-not-die), and (d) the conf-key surface + OpenMetrics export.
"""

import json

import numpy as np
import pytest

from mosaic_tpu import config as _config
from mosaic_tpu.config import ConfigError
from mosaic_tpu.functions.context import MosaicContext
from mosaic_tpu.obs import metrics
from mosaic_tpu.obs.openmetrics import to_openmetrics
from mosaic_tpu.sql import SQLSession
from mosaic_tpu.sql.engine import _vectorized_equi_join
from mosaic_tpu.sql.planner import (MISPREDICT_FACTOR, STATS_VERSION,
                                    Decision, Planner, planner)


@pytest.fixture(scope="module")
def mc():
    return MosaicContext.build("CUSTOM(-180,180,-90,90,2,360,180)")


@pytest.fixture(scope="module")
def session(mc):
    return SQLSession(mc)


@pytest.fixture()
def clean_config():
    prev = _config.default_config()
    yield
    _config.set_default_config(prev)


# --------------------------------------------------------- conf keys


def test_conf_keys_validate():
    cfg = _config.MosaicConfig()
    cfg = _config.apply_conf(cfg, "mosaic.stream.chunk.rows", "65536")
    assert cfg.stream_chunk_rows == 65536
    for bad in ("abc", "0", "-4"):
        with pytest.raises(ConfigError):
            _config.apply_conf(cfg, "mosaic.stream.chunk.rows", bad)
    for ok in ("auto", "brute", "ring", "2048"):
        assert _config.apply_conf(
            cfg, "mosaic.knn.strategy", ok).knn_strategy == ok
    with pytest.raises(ConfigError):
        _config.apply_conf(cfg, "mosaic.knn.strategy", "bogus")
    cfg = _config.apply_conf(cfg, "mosaic.planner.enabled", "false")
    assert cfg.planner_enabled is False
    cfg = _config.apply_conf(cfg, "mosaic.planner.stats.path",
                             "/tmp/ps.json")
    assert cfg.planner_stats_path == "/tmp/ps.json"


def test_planner_force_keys():
    cfg = _config.MosaicConfig()
    cfg = _config.apply_conf(cfg, "mosaic.planner.force.equi_join",
                             "loop")
    assert _config.planner_force_for(cfg, "equi_join") == "loop"
    assert _config.planner_force_for(cfg, "knn") == "auto"
    # "auto" clears the pin
    cfg = _config.apply_conf(cfg, "mosaic.planner.force.equi_join",
                             "auto")
    assert _config.planner_force_for(cfg, "equi_join") == "auto"
    with pytest.raises(ConfigError):
        _config.apply_conf(cfg, "mosaic.planner.force.bogus_op",
                           "loop")
    # the PIP join has no plannable strategies: its key selects nothing
    with pytest.raises(ConfigError):
        _config.apply_conf(cfg, "mosaic.planner.force.pip_join",
                           "streamed")
    with pytest.raises(ConfigError):
        _config.apply_conf(cfg, "mosaic.planner.force.knn",
                           "warp_drive")


def test_force_pins_decision(clean_config):
    _config.set_default_config(_config.apply_conf(
        _config.default_config(), "mosaic.planner.force.equi_join",
        "loop"))
    d = Planner().decide_equi_join(1 << 20, 1 << 10)
    assert d.strategy == "loop" and d.forced


# ------------------------------------------------- cost model mechanics


def test_cold_heuristics():
    p = Planner()
    assert p.decide_equi_join(100, 100).strategy == "loop"
    assert p.decide_equi_join(1 << 16, 1 << 10).strategy == \
        "vectorized"
    assert p.decide_knn(50, 64, default_max=128).strategy == "brute"
    assert p.decide_knn(50, 10_000, default_max=128).strategy == "ring"


def test_learned_costs_flip_strategy():
    """The deterministic feedback loop: feed observed wall times and
    the decision follows whichever strategy measured cheaper."""
    p = Planner()
    n = 8192
    p.observe_op("equi_join/loop", n, 0.100)        # 100 ms
    p.observe_op("equi_join/vectorized", n, 0.002)  # 2 ms
    assert p.decide_equi_join(n // 2, n // 2).strategy == "vectorized"
    for _ in range(8):  # EWMA needs a few samples to cross over
        p.observe_op("equi_join/loop", n, 0.001)
        p.observe_op("equi_join/vectorized", n, 0.300)
    assert p.decide_equi_join(n // 2, n // 2).strategy == "loop"


def test_nearest_bucket_fallback_and_cap():
    p = Planner()
    p.observe_op("knn/brute", 1024, 0.010)
    # a coefficient learned at 1k rows still informs an 8k estimate
    assert p.ms_per_row("knn/brute", 8192) is not None
    assert p.ms_per_row("knn/ring", 8192) is None
    # the store is bounded (LRU): flooding it never grows past the cap
    for i in range(3000):
        p.observe_op(f"op{i}", 64, 0.001)
    assert p.report()["ms_keys"] <= 1024


def test_estimate_error_and_mispredicts():
    p = Planner()
    assert p.observe_estimate("filter", 100, 100) == 1.0
    assert p.observe_estimate("filter", 100, 400) > MISPREDICT_FACTOR
    assert p.mispredicts == 1
    assert p.error_p95() > 1.0


# ------------------------------------------------------- persistence


def test_warm_start_roundtrip(tmp_path):
    path = str(tmp_path / "stats.json")
    p = Planner()
    p.observe_op("pip_join/streamed/c16", 32768, 0.050, rows_out=900)
    assert p.save(path) == path
    blob = json.load(open(path))
    assert blob["version"] == STATS_VERSION
    # a fresh process (fresh Planner) plans from the saved coefficients
    p2 = Planner(stats_path=path)
    got = p2.ms_per_row("pip_join/streamed/c16", 32768)
    assert got == pytest.approx(0.050 * 1e3 / 32768)
    assert p2.ratio("pip_join/streamed/c16", 32768) == \
        pytest.approx(900 / 32768)


def test_corrupt_stats_degrade_not_die(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json!!")
    p = Planner(stats_path=str(bad))   # must not raise
    assert p.ms_per_row("pip_join/monolithic", 100) is None
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"version": 99, "ms_per_row": {}}))
    p2 = Planner(stats_path=str(wrong))
    assert p2.report()["ms_keys"] == 0
    # missing file: silently cold, and save() creates parent dirs
    p3 = Planner(stats_path=str(tmp_path / "sub" / "new.json"))
    p3.observe_op("knn/ring", 128, 0.001)
    assert p3.save() is not None


def test_stats_path_resolution(tmp_path, monkeypatch):
    path = str(tmp_path / "env.json")
    Planner(stats_path=path).save(path)
    monkeypatch.setenv("MOSAIC_TPU_PLANNER_STATS", path)
    p = Planner()
    assert p.configure_stats() == path  # env var wins over conf


# ------------------------------------------- pure strategy transform


def test_vectorized_join_matches_loop_reference(rng):
    """The sort-join must emit the exact pair sequence of the dict
    loop: left ascending, right index-ascending within each key."""
    for n, m, hi in [(50, 40, 8), (500, 300, 50), (1000, 1000, 2000)]:
        lk = rng.integers(0, hi, n)
        rk = rng.integers(0, hi, m)
        li, ri = _vectorized_equi_join(lk, rk)
        rmap = {}
        for j, k in enumerate(rk.tolist()):
            rmap.setdefault(k, []).append(j)
        eli, eri = [], []
        for i, k in enumerate(lk.tolist()):
            for j in rmap.get(k, ()):
                eli.append(i)
                eri.append(j)
        assert li.tolist() == eli
        assert ri.tolist() == eri


def test_forced_strategies_bit_identical(session, clean_config):
    rng = np.random.default_rng(3)
    n = 5000
    session.create_table("pl", {
        "k": rng.integers(0, 200, n).astype(np.int64),
        "v": rng.random(n)})
    session.create_table("pr", {
        "k": np.arange(200, dtype=np.int64),
        "w": rng.random(200)})
    q = ("SELECT pl.k AS k, v, w FROM pl JOIN pr ON pl.k = pr.k "
         "ORDER BY v LIMIT 500")
    outs = {}
    for strat in ("loop", "vectorized"):
        _config.set_default_config(_config.apply_conf(
            _config.default_config(),
            "mosaic.planner.force.equi_join", strat))
        outs[strat] = session.sql(q)
    for col in outs["loop"].columns:
        assert np.array_equal(outs["loop"].columns[col],
                              outs["vectorized"].columns[col]), col


def test_vectorized_ineligible_keys_fall_back(session, clean_config):
    """NaN float keys and composite keys are outside the sort-join's
    equality semantics — a forced "vectorized" pick must fall back to
    the loop and still return the loop's exact rows."""
    session.create_table("nl", {
        "k": np.array([1.0, np.nan, 2.0, np.nan, 3.0]),
        "a": np.arange(5.0)})
    session.create_table("nr", {
        "k": np.array([np.nan, 2.0, 3.0, 1.0]),
        "b": np.arange(4.0)})
    session.create_table("cl", {
        "k1": np.array([1, 1, 2, 2], np.int64),
        "k2": np.array([0, 1, 0, 1], np.int64),
        "a": np.arange(4.0)})
    session.create_table("cr", {
        "k1": np.array([2, 1], np.int64),
        "k2": np.array([1, 1], np.int64),
        "b": np.array([10.0, 20.0])})
    queries = [
        "SELECT a, b FROM nl JOIN nr ON nl.k = nr.k ORDER BY a",
        "SELECT a, b FROM cl JOIN cr ON cl.k1 = cr.k1 "
        "AND cl.k2 = cr.k2 ORDER BY a",
    ]
    outs = {}
    for strat in ("loop", "vectorized"):
        _config.set_default_config(_config.apply_conf(
            _config.default_config(),
            "mosaic.planner.force.equi_join", strat))
        outs[strat] = [session.sql(q) for q in queries]
    for a, b in zip(outs["loop"], outs["vectorized"]):
        for col in a.columns:
            assert np.array_equal(a.columns[col], b.columns[col]), col
    # NaN keys never match (dict-loop semantics preserved)
    assert len(outs["loop"][0]) == 3


def test_planner_off_bit_identical(session, clean_config):
    rng = np.random.default_rng(11)
    n = 6000
    session.create_table("po", {
        "k": rng.integers(0, 64, n).astype(np.int64),
        "v": rng.random(n)})
    q = ("SELECT k, count(*) AS c, sum(v) AS s FROM po "
         "WHERE v > 0.5 GROUP BY k ORDER BY k")
    on = session.sql(q)
    _config.set_default_config(_config.apply_conf(
        _config.default_config(), "mosaic.planner.enabled", "false"))
    off = session.sql(q)
    for col in on.columns:
        assert np.array_equal(on.columns[col], off.columns[col]), col


# --------------------------------------------------- feedback loop


def test_estimate_error_converges_after_three_runs(mc):
    """The acceptance bar: running the same workload 3 times, the
    estimate-error p95 over the LAST run's closed estimates is < 2x
    (the planner learned the workload's selectivities/fanouts)."""
    planner.reset()
    rng = np.random.default_rng(5)
    n = 4000

    def run_workload():
        s = SQLSession(mc)
        s.create_table("wl", {
            "k": rng.integers(0, 100, n).astype(np.int64),
            "v": rng.random(n)})
        s.create_table("wr", {
            "k": np.arange(100, dtype=np.int64),
            "w": rng.random(100)})
        s.sql("SELECT k, v FROM wl WHERE v > 0.75 ORDER BY v")
        s.sql("SELECT wl.k AS k, v, w FROM wl JOIN wr ON wl.k = wr.k")
        s.sql("SELECT k, count(*) AS c FROM wl GROUP BY k")

    run_workload()
    run_workload()
    before = len(planner.error_history)
    run_workload()
    last_run = list(planner.error_history)[before:]
    assert last_run, "third run closed no estimates"
    p95 = float(np.percentile(last_run, 95))
    assert p95 < MISPREDICT_FACTOR, last_run
    assert planner.report()["decisions"] > 0


# ------------------------------------------------------ observability


def test_planner_metrics_in_openmetrics():
    was = metrics.enabled
    metrics.enable()
    try:
        planner.record_decision(Decision(
            "pip_join", "streamed", "test", 100, key_n=100))
        planner.observe_estimate("pip_join", 100, 90)
        text = to_openmetrics()
        assert "mosaic_planner_decisions_total" in text
        assert "mosaic_planner_estimate_error" in text
    finally:
        if not was:
            metrics.disable()
