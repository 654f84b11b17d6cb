"""Continuous per-device attribution: memory, bytes, busy-time.

Reference counterpart: the Spark executor page — per-executor task
time, shuffle bytes, peak memory.  Standalone the "executors" are mesh
devices, and nothing in JAX hands us per-device *time* on the host
side, so the monitor attributes **wall time to devices by observed
load share**: a sharded operator that ran ``seconds`` of wall clock
with per-shard matched-row counts ``w`` charges device ``i`` with
``seconds * w[i] / sum(w)``.  That is exactly the quantity the
skew gauges already measure — a device holding 3x the rows of its
peers accrues 3x the busy time — and it needs no extra host syncs:
the weights come from results the joins already bring back to the
host.

Feeds, all folded here:

* ``attribute(op, seconds, weights)`` — sharded pip_join / overlay
  wall time, split per device (also kept per-operator for the
  EXPLAIN ANALYZE ``device_ms`` column);
* ``observe_rows(site, counts)`` — per-device row counts from the
  overlay exchange accounting (``device/rows/<dev>`` counters);
* ``sample(store)`` — the sampler-tick fold: refreshes
  ``sample_memory`` watermarks (so ``mem/*`` gauges populate
  continuously), then writes per-device busy/peak series and a
  ``device/util/<dev>`` utilization gauge (busy-share since the
  previous tick, clamped to [0, 1]).

Everything is a no-op while the metrics registry is disabled — same
one-check contract as the rest of ``obs``.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List, Mapping, Optional, Sequence

from .metrics import metrics

__all__ = ["DeviceMonitor", "devicemon", "mesh_device_keys",
           "format_device_ms"]


def mesh_device_keys(mesh) -> List[str]:
    """``platform:id`` keys for a mesh's devices in flat (shard)
    order — the key spelling ``sample_memory`` gauges use."""
    return [f"{d.platform}:{d.id}" for d in mesh.devices.flat]


def _default_keys(n: int) -> List[str]:
    """Device keys when the caller has no mesh handy: the visible jax
    devices if they cover ``n`` shards, else positional ``shard:<i>``."""
    if "jax" in sys.modules:
        try:
            import jax
            devs = jax.devices()
            if len(devs) >= n:
                return [f"{d.platform}:{d.id}" for d in devs[:n]]
        except Exception:
            pass
    return [f"shard:{i}" for i in range(n)]


class DeviceMonitor:
    """Process-global per-device busy-time / row / memory fold."""

    def __init__(self):
        self._lock = threading.Lock()
        self._busy: Dict[str, float] = {}          # dev -> seconds
        self._op_dev: Dict[str, Dict[str, float]] = {}  # op -> dev -> s
        self._rows: Dict[str, float] = {}          # dev -> rows routed
        self._last_tick: Optional[float] = None
        self._last_busy: Dict[str, float] = {}

    def reset(self) -> None:
        with self._lock:
            self._busy.clear()
            self._op_dev.clear()
            self._rows.clear()
            self._last_tick = None
            self._last_busy.clear()

    # -- attribution feeds -------------------------------------------
    def attribute(self, op: str, seconds: float,
                  weights: Optional[Sequence[float]] = None,
                  devices: Optional[Sequence[str]] = None) -> None:
        """Charge ``seconds`` of wall time to devices proportional to
        ``weights`` (uniform when None/degenerate)."""
        if not metrics.enabled or seconds <= 0:
            return
        if weights is None and devices is None:
            devices = _default_keys(1)
        if devices is None:
            devices = _default_keys(len(weights))
        ws = [max(0.0, float(w)) for w in weights] \
            if weights is not None else [1.0] * len(devices)
        if len(ws) != len(devices) or not devices:
            return
        total = sum(ws)
        if total <= 0:
            ws = [1.0] * len(devices)
            total = float(len(devices))
        with self._lock:
            per_op = self._op_dev.setdefault(op, {})
            for dev, w in zip(devices, ws):
                share = seconds * w / total
                self._busy[dev] = self._busy.get(dev, 0.0) + share
                per_op[dev] = per_op.get(dev, 0.0) + share

    def observe_rows(self, site: str,
                     counts: Sequence[float]) -> None:
        """Per-device routed-row counts from an exchange (the overlay
        accounting's hash-destination bincount)."""
        if not metrics.enabled:
            return
        devices = _default_keys(len(counts))
        with self._lock:
            for dev, c in zip(devices, counts):
                self._rows[dev] = self._rows.get(dev, 0.0) + float(c)
        for dev, c in zip(devices, counts):
            metrics.count(f"device/rows/{dev}", float(c))

    # -- reads --------------------------------------------------------
    def op_device_totals(self) -> Dict[str, Dict[str, float]]:
        """op -> device -> attributed seconds (cumulative); the
        EXPLAIN ANALYZE ``device_ms`` column diffs this around each
        stage."""
        with self._lock:
            return {op: dict(d) for op, d in self._op_dev.items()}

    def busy_by_device(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._busy)

    # -- the sampler-tick fold ---------------------------------------
    def sample(self, store=None, now: Optional[float] = None) -> None:
        """One fold pass: refresh memory watermarks, emit per-device
        series + utilization gauges.  Never initializes a jax backend
        (memory sampling is skipped until jax is already imported)."""
        if not metrics.enabled:
            return
        now = time.time() if now is None else now
        if store is None:
            from .timeseries import timeseries as store
        if "jax" in sys.modules:
            try:
                from .jaxmon import sample_memory
                mem = sample_memory()
            except Exception:
                mem = {}
            for dev, st in mem.items():
                store.record(f"device/peak_bytes/{dev}",
                             float(st.get("peak_bytes") or 0.0), now)
        with self._lock:
            busy = dict(self._busy)
            rows = dict(self._rows)
            last_tick, last_busy = self._last_tick, dict(self._last_busy)
            self._last_tick = now
            self._last_busy = dict(busy)
        for dev, s in busy.items():
            store.record(f"device/busy_s/{dev}", s, now)
        for dev, r in rows.items():
            store.record(f"device/rows/{dev}", r, now)
        if last_tick is not None and now > last_tick:
            dt = now - last_tick
            for dev, s in busy.items():
                util = (s - last_busy.get(dev, 0.0)) / dt
                metrics.gauge(f"device/util/{dev}",
                              min(1.0, max(0.0, util)))

    def report(self) -> Dict[str, object]:
        with self._lock:
            busy = dict(self._busy)
            rows = dict(self._rows)
            ops = {op: dict(d) for op, d in self._op_dev.items()}
        gauges = metrics.report()["gauges"]
        try:
            from .memwatch import memwatch
            live = memwatch.live_by_device() if memwatch.enabled else {}
        except Exception:
            live = {}
        devs = sorted(set(busy) | set(rows) | set(live))
        return {
            "devices": {
                dev: {
                    "busy_s": busy.get(dev, 0.0),
                    "rows": rows.get(dev, 0.0),
                    "util": gauges.get(f"device/util/{dev}", 0.0),
                    "peak_bytes": gauges.get(f"mem/peak_bytes/{dev}"),
                    # ledger-attributed live bytes + pressure (the
                    # allocator peak above is the backend's view; this
                    # is what WE can name a holder for)
                    "live_bytes": int(live.get(dev, 0)),
                    "pressure": gauges.get(f"mem/pressure/{dev}", 0.0),
                } for dev in devs
            },
            "ops": ops,
        }


#: the process-global monitor
devicemon = DeviceMonitor()


def format_device_ms(delta: Mapping[str, float]) -> str:
    """Render a per-device seconds delta as the EXPLAIN ANALYZE
    ``device_ms`` cell: ``"cpu:0=1.2 cpu:1=1.1"`` (ms), ``"-"`` when
    nothing was attributed."""
    parts = [f"{dev}={delta[dev] * 1e3:.1f}"
             for dev in sorted(delta) if delta[dev] > 0]
    return " ".join(parts) if parts else "-"
