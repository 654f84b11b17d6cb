"""Serving-fleet supervisor (``serve/supervisor.py``) on fake workers.

Every test here swaps ``worker_cmd`` for a tiny jax-free stub that
writes its ready file and sleeps, so the supervisor's control plane —
spawn/ready bookkeeping, crash detection + backoff respawn, the
crash-loop circuit breaker, SIGTERM drain with the bounded hard-kill
path, and the ``serve.spawn`` fault site — is exercised in
milliseconds.  The end-to-end fleet (real ``QueryServer`` workers,
kill drill, warm-cache respawn) runs in the fleet-smoke and
fleet-chaos CI lanes.
"""

import json
import os
import signal
import socket
import sys
import textwrap
import time

import pytest

from mosaic_tpu import config as _config
from mosaic_tpu.obs import metrics
from mosaic_tpu.obs.recorder import recorder
from mosaic_tpu.resilience import faults
from mosaic_tpu.serve.supervisor import (SCOREBOARD_FILE,
                                         SUPERVISOR_FILE, ServeFleet)

pytestmark = pytest.mark.skipif(os.name != "posix",
                                reason="fleet supervisor is POSIX")

#: a worker that comes up instantly: ready file, then sleep; exits 0
#: on SIGTERM like a draining QueryServer would
_STUB = textwrap.dedent("""
    import json, os, signal, sys, time
    d = os.environ["MOSAIC_FLEET_DIR"]
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
    with open(os.path.join(d, "ready-%d.json" % os.getpid()), "w") as f:
        json.dump({"pid": os.getpid()}, f)
    time.sleep(120)
""")

#: a worker that refuses to drain: SIGTERM is ignored
_STUB_DEAF = _STUB.replace(
    "lambda *a: sys.exit(0)", "signal.SIG_IGN")

#: a worker that dies before ever becoming ready
_STUB_DOA = "import sys; sys.exit(3)"


def _stub_cmd(src=_STUB):
    return [sys.executable, "-c", src]


@pytest.fixture
def fleet_env():
    prev = _config.default_config()
    metrics.reset()
    metrics.enable()
    recorder.reset()
    recorder.enable()
    yield
    faults.disarm()
    _config.set_default_config(prev)
    metrics.disable()
    metrics.reset()
    recorder.reset()


def _conf(**keys):
    cfg = _config.default_config()
    for k, v in keys.items():
        cfg = _config.apply_conf(cfg, k.replace("_", "."), str(v))
    _config.set_default_config(cfg)


def _counter(name):
    return metrics.report()["counters"].get(name, 0)


def _events(name):
    return recorder.events(name)


def _fleet(tmp_path, workers=2, stub=_STUB, **kw):
    return ServeFleet(workers=workers, port=0,
                      fleet_dir=str(tmp_path / "fleet"),
                      worker_cmd=_stub_cmd(stub), **kw)


# --------------------------------------------------------- lifecycle

def test_start_ready_status_stop(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0)    # tests drive tick()
    fleet = _fleet(tmp_path, workers=2)
    with fleet:
        assert len(fleet.worker_pids()) == 2
        st = fleet.status()
        assert st["live"] == 2 and st["degraded"] == 0
        assert all(w["ready"] for w in st["workers"])
        assert _counter("serve/worker_spawns") == 2
        assert len(_events("fleet_worker_spawn")) == 2
        # the fleet dir carries the whole control plane
        names = os.listdir(fleet.fleet_dir)
        assert SCOREBOARD_FILE in names and SUPERVISOR_FILE in names
    # clean drain: stubs exit on SIGTERM, nothing was forced
    assert _counter("serve/drain_forced") == 0
    assert fleet.worker_pids() == []
    disk = json.load(open(os.path.join(fleet.fleet_dir,
                                       SUPERVISOR_FILE)))
    assert disk["stopping"] is True and disk["live"] == 0


def test_no_worker_ready_raises(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0)
    fleet = _fleet(tmp_path, workers=2, stub=_STUB_DOA)
    with pytest.raises(RuntimeError, match="no fleet worker"):
        fleet.start(ready_timeout_s=10)


@pytest.mark.parametrize("platforms, raises", [("", True),
                                                ("cpu", False)])
def test_parent_holding_tpu_fails_fast(tmp_path, fleet_env, monkeypatch,
                                       platforms, raises):
    """A parent that holds the chip refuses to spawn workers that would
    need it, unless they are pinned to the CPU."""
    from mosaic_tpu.serve import supervisor as sup
    assert not sup._parent_holds_tpu()        # this suite runs on CPU
    _conf(mosaic_serve_fleet_health_ms=0)
    monkeypatch.setattr(sup, "_parent_holds_tpu", lambda: True)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    fleet = _fleet(tmp_path, workers=1)
    if raises:
        with pytest.raises(RuntimeError, match="holds the TPU"):
            fleet.start()
        assert fleet.worker_pids() == []
    else:
        with fleet:
            assert len(fleet.worker_pids()) == 1


def test_parent_socket_fallback_mode(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0)
    with _fleet(tmp_path, workers=1,
                force_parent_socket=True) as fleet:
        assert fleet.mode == "parent_socket"
        # the parent holds a real listener: connects are accepted
        # (queued) even though the stub never calls accept()
        with socket.create_connection(("127.0.0.1", fleet.port),
                                      timeout=5):
            pass
    # stop() closed it
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", fleet.port),
                                 timeout=0.5)


# ------------------------------------------------- crash -> respawn

def test_crash_respawns_through_backoff(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0)
    with _fleet(tmp_path, workers=2) as fleet:
        victim = fleet.worker_pids()[0]
        os.kill(victim, signal.SIGKILL)
        deadline = time.time() + 1.0      # let the kernel reap it
        while time.time() < deadline:
            fleet.tick()
            if _counter("serve/worker_crashes"):
                break
            time.sleep(0.02)
        assert _counter("serve/worker_crashes") == 1
        assert len(_events("fleet_worker_exit")) == 1
        # parked until the backoff is due; a far-future tick respawns
        fleet.tick(now=time.time() + 60.0)
        pids = fleet.worker_pids()
        assert len(pids) == 2 and victim not in pids
        assert _counter("serve/worker_respawns") == 1
        st = fleet.status()
        assert st["degraded"] == 0
        assert [w for w in st["workers"]
                if w["restarts"] == 1] != []


def test_breaker_parks_slot_and_fleet_survives(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0,
          mosaic_serve_fleet_restart_max=1,
          mosaic_serve_fleet_restart_window_ms=600_000)
    with _fleet(tmp_path, workers=2) as fleet:
        for round_ in range(2):           # crash 1 respawns; 2 trips
            victim = fleet.status()["workers"][0]["pid"]
            os.kill(victim, signal.SIGKILL)
            deadline = time.time() + 1.0
            while time.time() < deadline:
                fleet.tick(now=time.time() + 60.0 * (round_ + 1))
                ws = fleet.status()["workers"][0]
                if ws["degraded"] or (ws["alive"] and
                                      ws["pid"] != victim):
                    break
                time.sleep(0.02)
        st = fleet.status()
        assert st["degraded"] == 1
        assert st["live"] == 1            # degraded = run at N-1
        assert _counter("serve/fleet_degraded") == 1
        evs = _events("fleet_degraded")
        assert len(evs) == 1 and evs[0]["index"] == 0
        # the breaker holds: more ticks never resurrect the slot
        fleet.tick(now=time.time() + 600.0)
        assert fleet.status()["live"] == 1
        assert _counter("serve/fleet_degraded") == 1


# ------------------------------------------------------- drain paths

def test_sigterm_ignoring_worker_is_force_killed(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0,
          mosaic_serve_drain_ms=200)
    fleet = _fleet(tmp_path, workers=2, stub=_STUB_DEAF)
    fleet.start()
    pids = fleet.worker_pids()
    t0 = time.time()
    fleet.stop(drain=True)
    assert _counter("serve/drain_forced") == 2
    assert time.time() - t0 < 10.0        # bounded, not a hang
    for pid in pids:
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)


def test_signal_handler_drains_fleet(tmp_path, fleet_env):
    _conf(mosaic_serve_fleet_health_ms=0)
    fleet = _fleet(tmp_path, workers=1)
    fleet.start()
    fleet.install_signal_handlers()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        assert fleet.wait(timeout=10.0)
        deadline = time.time() + 5.0
        while fleet.worker_pids() and time.time() < deadline:
            time.sleep(0.05)
        assert fleet.worker_pids() == []
        assert _counter("serve/drain_forced") == 0
    finally:
        fleet.stop()


# ------------------------------------------------------ spawn chaos

def test_spawn_fault_is_retried(tmp_path, fleet_env, fault_plan):
    _conf(mosaic_serve_fleet_health_ms=0)
    fault_plan("seed=5;site=serve.spawn,fails=1,error=OSError")
    with _fleet(tmp_path, workers=2) as fleet:
        # first exec raised, SERVE_SPAWN_RETRY recovered it
        assert len(fleet.worker_pids()) == 2
        assert _counter("retry/recovered/serve.spawn") == 1
        assert _counter("serve/worker_spawns") == 2


def test_spawn_fault_exhaustion_counts_failure(tmp_path, fleet_env,
                                               fault_plan):
    """Every attempt for one slot fails: the slot books a spawn
    failure and the OTHER worker still comes up — degrade, not die."""
    _conf(mosaic_serve_fleet_health_ms=0)
    fault_plan("seed=5;site=serve.spawn,fails=3,error=OSError")
    fleet = _fleet(tmp_path, workers=2)
    with fleet:
        assert _counter("serve/worker_spawn_failures") == 1
        assert _counter("retry/giveups/serve.spawn") == 1
        assert len(fleet.worker_pids()) == 1


# -------------------------------------------------------- reap tick

def test_tick_reaps_dead_scoreboard_claims(tmp_path, fleet_env):
    from mosaic_tpu.serve.scoreboard import Scoreboard
    _conf(mosaic_serve_fleet_health_ms=0,
          mosaic_serve_fleet_reap_ms=0)     # reap on every tick
    with _fleet(tmp_path, workers=1) as fleet:
        sb_path = os.path.join(fleet.fleet_dir, SCOREBOARD_FILE)
        victim = fleet.worker_pids()[0]
        with Scoreboard(sb_path) as mine:
            # plant a claim owned by the worker, then kill the worker
            tok, deny = mine.admit("t", 0, 0)
            assert deny is None
            import struct as _struct
            from mosaic_tpu.serve import scoreboard as _sbmod
            off = _sbmod._HEADER_SIZE + tok.index * _sbmod._SLOT_SIZE
            with open(sb_path, "r+b") as f:
                raw = bytearray(_sbmod._SLOT.pack(
                    tok.seq, 1, victim, time.time(),
                    b"t".ljust(44, b"\0")))
                f.seek(off)
                f.write(bytes(raw))
            os.kill(victim, signal.SIGKILL)
            deadline = time.time() + 2.0
            while time.time() < deadline:
                fleet.tick()
                if mine.counts("t")["concurrency"] == 0:
                    break
                time.sleep(0.02)
            assert mine.counts("t")["concurrency"] == 0
