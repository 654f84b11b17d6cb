"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything here is driven by data: the cell names a configuration
(``BENCHMARK.json`` ``configs[].file``) and a traffic mix
(``benchmark/traffic/<mix>.json``); each per-layer metric is a reader
``benchmark/layer_metrics/<metric>.py`` found by its name.

The system under test is the program's public streamed join:
``tessellate`` and ``build_pip_index`` in set-up, then the ``run``
of ``make_streamed_pip_join`` on one chip.  The program receives only
the generated points.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import traceback
from typing import Callable, Optional

import numpy as np

import reference
import tracereduce
import traffic
import zones as zonesets

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
ZONE_CACHE = os.path.join(BENCH_DIR, ".zones")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Counts the persistent compile cache's hits and misses, and while
    armed JAX compile events and the seconds the garbage collector ran,
    so that either inside the measured window shows on standard
    error."""

    def __init__(self):
        import jax.monitoring
        self.armed, self.count, self.seconds = False, 0, 0.0
        self.gc_seconds, self._gc_t0 = 0.0, 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._event)
        jax.monitoring.register_event_listener(self._cache_event)
        gc.callbacks.append(self._gc)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if self.armed and ("compile" in event or "cache" in event):
            self.count += 1
            self.seconds += duration

    def _cache_event(self, event: str, **_kw) -> None:
        for key in self.cache:
            if event == f"/jax/compilation_cache/cache_{key}":
                self.cache[key] += 1

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.armed:
            self.gc_seconds += time.perf_counter() - self._gc_t0

    def arm(self) -> None:
        self.count, self.seconds, self.gc_seconds = 0, 0.0, 0.0
        self.armed = True

    def disarm(self) -> None:
        self.armed = False


def load_cell(name: str):
    """(bench, cell, config, mix spec) for the cell called ``name``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    if cell["chips"] != 1:
        raise SystemExit(f"benchmark: cell {name} asks for {cell['chips']} "
                         f"chips; this harness drives the one-chip entry")
    if config["chips"] != cell["chips"]:
        raise SystemExit(f"benchmark: cell {name} asks for {cell['chips']} "
                         f"chips, its configuration for {config['chips']}")
    return bench, cell, config, mix


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def to_geometry(zone_rings):
    """The zone rings as the program's GeometryArray (its loader)."""
    from mosaic_tpu.core.geometry.array import GeometryBuilder
    b = GeometryBuilder()
    for zone in zone_rings:
        if len(zone) == 1:
            b.add_polygon(zone[0][0], zone[0][1:])
        else:
            b.add_multipolygon(zone)
    return b.finish()


def build_program(config: dict, zone_rings, timers: dict):
    """Set-up of the system under test: tessellation, index build and
    the streamed join.  Returns ``run``."""
    from mosaic_tpu.core.index.factory import get_index_system
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.parallel.pip_join import (build_pip_index,
                                              make_streamed_pip_join)
    polys = to_geometry(zone_rings)
    grid = get_index_system(config["grid"])
    res = int(config["resolution"])
    t0 = time.perf_counter()
    chips = tessellate(polys, res, grid, keep_core_geom=False)
    timers["tessellate_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = build_pip_index(polys, res, grid, chips=chips)
    timers["index_build_s"] = time.perf_counter() - t0
    log(f"zones {len(polys)} -> {len(chips)} chips at {config['grid']} res "
        f"{res}; index {type(idx).__name__}")
    return make_streamed_pip_join(idx, grid, polys=polys,
                                  chunk=config["chunk_rows"],
                                  precision=config["precision"])


def warm_shapes(n: int, chunk: int):
    """Row counts of the launches one request of ``n`` points makes."""
    shapes = [min(n, chunk)]
    if n > chunk and n % chunk:
        shapes.append(n % chunk)
    return shapes


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def read_layer_metric(name: str, record: dict):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def capped(seed: int, total: int, cap: int) -> np.ndarray:
    """Which of ``total`` sampled answers are compared: all of them, or
    ``cap`` drawn from the seed."""
    if total <= cap:
        return np.arange(total)
    return np.sort(traffic.rng_of(seed, 0, traffic.CAP).choice(
        total, cap, replace=False))


def check_answers(ref, records, seed: int, cap: int) -> dict:
    """Compare the sampled answers of the window's requests with the
    reference: how many differ, and in how many requests."""
    if not records:
        return {"compared": 0, "mismatched": 0, "bad_requests": 0}
    got = np.concatenate([r["zones"] for r in records])
    pts = np.concatenate([r["points"] for r in records])
    req = np.repeat(np.arange(len(records)),
                    [len(r["zones"]) for r in records])
    keep = capped(seed, len(got), cap)
    wrong = got[keep] != ref.zones_of(pts[keep])
    return {"compared": int(len(keep)), "mismatched": int(wrong.sum()),
            "bad_requests": int(len(np.unique(req[keep][wrong])))}


class Prepared:
    """A cell set up once: its program built and warmed, ready for one
    measured window (``run_cell``) or several (``proof.py``)."""

    def __init__(self, name: str, seed: int, t_process: float,
                 require_chip: bool = True,
                 overrides: Optional[dict] = None):
        self.name, self.t_process = name, t_process
        self.bench, self.cell, self.config, spec = load_cell(name)
        spec = {**spec, **(overrides or {})}
        import jax
        devices = jax.devices()
        if require_chip and (devices[0].platform == "cpu"
                             or len(devices) < self.cell["chips"]):
            raise SystemExit(
                f"benchmark: cell {name} needs {self.cell['chips']} "
                f"accelerator chip(s); JAX sees {len(devices)} "
                f"{devices[0].platform} device(s)")
        self.devices, self.used = devices, devices[:self.cell["chips"]]
        import mosaic_tpu  # noqa: F401  (places the compile cache)
        self.compiles = CompileWatch()
        self.phases = {"imports": time.perf_counter() - t_process}
        self.timers: dict = {}
        t0 = time.perf_counter()
        self.zone_rings = zonesets.load(self.config["zones"], ZONE_CACHE)
        if len(self.zone_rings) != self.config["zone_count"]:
            raise SystemExit(
                f"benchmark: {len(self.zone_rings)} zones, the "
                f"configuration states {self.config['zone_count']}")
        self.phases["zones"] = time.perf_counter() - t0
        self.mix = traffic.Mix(spec, zonesets.bbox(self.zone_rings))
        self.run = build_program(self.config, self.zone_rings, self.timers)
        t0 = time.perf_counter()
        for k, rows in enumerate(warm_shapes(self.mix.n,
                                             self.config["chunk_rows"])):
            self.run(self.mix.points(seed, k, traffic.WARMUP, n=rows))
        self.phases["warm_up"] = time.perf_counter() - t0


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_process: float, require_chip: bool = True,
             wrap: Optional[Callable] = None,
             overrides: Optional[dict] = None) -> dict:
    """One run of cell ``name``; returns the result object.

    ``require_chip=False`` and ``overrides`` (mix keys to replace, for
    a small size) serve the tests on the CPU, and ``wrap`` lets them
    break the timed path; the command line offers none of these."""
    prep = Prepared(name, seed, t_process, require_chip, overrides)
    return measure(prep, seed, seconds, trace, wrap, free=True)


def measure(prep: Prepared, seed: int, seconds: float, trace: bool,
            wrap: Optional[Callable] = None, free: bool = False) -> dict:
    """One measured window of ``prep``'s cell, then the check of its
    answers.  ``wrap`` puts a stand-in around the timed path; ``free``
    drops the program before the reference runs (a single run)."""
    import jax
    from mosaic_tpu.obs import metrics
    name, bench, config, mix = prep.name, prep.bench, prep.config, prep.mix
    zone_rings, used, devices = prep.zone_rings, prep.used, prep.devices
    timers, phases, compiles = prep.timers, prep.phases, prep.compiles
    run = wrap(prep.run) if wrap is not None else prep.run
    if trace:
        metrics.enable()
    feeder = traffic.Feeder(mix, seed)
    feeder.fill()

    ann = jax.profiler.TraceAnnotation if trace \
        else (lambda _n: contextlib.nullcontext())
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    counters0 = metrics.report()["counters"] if trace else {}
    if trace:
        # the benchmark's own marks only: no Python or runtime host events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    records, lat, starts, failed, sent, error = [], [], [], 0, 0, None
    t_start = time.perf_counter()
    setup_s = t_start - prep.t_process
    compiles.arm()
    t_end = t_start
    with ann("bench/window"):
        while t_end - t_start < seconds:
            with ann("bench/generate"):
                i, pts = feeder.get()
            with ann("bench/between"):
                pos = mix.sample(seed, i, len(pts), config["check_fraction"])
                sample = pts[pos]
            t0 = time.perf_counter()
            starts.append(t0 - t_start)
            sent += 1
            try:
                with ann("bench/request"):
                    zone, rechecked = run(pts)
            except Exception:
                failed += 1
                error = traceback.format_exc()
                t_end = time.perf_counter()
                break
            t_end = time.perf_counter()
            with ann("bench/between"):
                lat.append(t_end - t0)
                zone = np.asarray(zone)
                if zone.shape != (len(pts),):
                    failed += 1
                    continue
                records.append({"n": len(pts), "points": sample,
                                "zones": zone[pos].astype(np.int64),
                                "rechecked": int(rechecked)})
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    compiles.disarm()
    counters = {}
    if trace:
        after = metrics.report()["counters"]
        counters = {c: v - counters0.get(c, 0.0) for c, v in after.items()}
    feeder.close()
    waited = feeder.waited
    memory = peak_bytes(used)
    del run
    if free:
        prep.run = None
        gc.collect()
    if error:
        log(error)
    log(f"set-up {setup_s:.6f} s: " + ", ".join(
        f"{k} {v:.6f}" for k, v in {**phases, **timers}.items()))
    log(f"persistent compile cache, whole run: {compiles.cache['hits']} "
        f"hits, {compiles.cache['misses']} misses")
    log(f"compile events in the window: {compiles.count} "
        f"({compiles.seconds:.6f} s); garbage collection "
        f"{compiles.gc_seconds:.6f} s")
    if lat:
        worst = int(np.argmax(lat))
        log(f"slowest request: #{worst} {lat[worst]:.6f} s")
        slow = [(k, starts[k], v) for k, v in enumerate(lat)
                if v > 10 * statistics.median(lat)]
        if slow:
            log("requests over 10x the median: " + "; ".join(
                f"#{k} sent at {t:.3f} s took {v:.6f} s"
                for k, t, v in slow[:20]))
    made = feeder.made
    log(f"generator: {len(waited)} requests taken, waited {sum(waited):.6f}"
        f" s in all, at most {max(waited, default=0.0):.6f} s; "
        f"{len(made)} made on {traffic.WORKERS} threads, median "
        f"{statistics.median(made) if made else 0.0:.6f} s, at most "
        f"{max(made, default=0.0):.6f} s each")

    ref = reference.Reference(zone_rings)
    t0 = time.perf_counter()
    chk = check_answers(ref, records, seed, config["check_answers_max"])
    log(f"reference: {chk['compared']} answers of {len(records)} requests "
        f"checked in {time.perf_counter() - t0:.3f} s")
    failed += chk["bad_requests"]
    checks = {"mismatched_answers": {"value": chk["mismatched"], "limit": 0},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = bool(records) and all(
        c["value"] <= c["limit"] for c in checks.values())

    points = int(sum(r["n"] for r in records))
    record = {"timers": timers, "counters": counters, "points": points,
              "requests": len(records), "window_s": window_s,
              "rechecked": int(sum(r["rechecked"] for r in records)),
              "generator_wait_s": sum(waited), "trace": None}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": correct, "attempted": sent, "failed": failed}
    if trace:
        files = [os.path.join(dp, f) for dp, _, fs in os.walk(tdir)
                 for f in fs if f.endswith(".xplane.pb")]
        summary = tracereduce.reduce(*tracereduce.read_xplane(files[0])) \
            if files else None
        shutil.rmtree(tdir, ignore_errors=True)
        record["trace"] = summary
        values = {}
        for m in bench["per_layer"]:
            if applies(m, name):
                v = read_layer_metric(m["name"], record)
                if v is not None:
                    values[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = values
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            out["breakdown"] = {"device_ops": summary["device_ops"],
                                "idle_gaps": summary["idle_gaps"]}
    else:
        e2e = {"setup_s": setup_s,
               "join_pts_per_s": points / window_s if window_s else 0.0}
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in bench["end_to_end"] if applies(m, name)}
        if lat:
            q = statistics.quantiles(np.asarray(lat) * 1e3, n=4)
            log(f"requests: {len(lat)} in {window_s:.6f} s; latency ms "
                f"quartiles {q[0]:.6f} {q[1]:.6f} {q[2]:.6f}")
    out["device"] = device
    for key, c in checks.items():
        log(f"check {key}: {c['value']} (limit {c['limit']})")
    out["checks"] = checks
    return out
