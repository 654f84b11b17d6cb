"""Configuration system for mosaic_tpu.

TPU-native analogue of the reference's ``MosaicExpressionConfig``
(reference: functions/MosaicExpressionConfig.scala:19-117) and the conf-key
namespace in mosaic/package.scala:21-43.  Instead of Spark confs serialized
into Catalyst expressions, we keep an immutable dataclass that every op
receives (or reads from a context-local default).  It is a plain pytree leaf
holder — safe to close over in jitted functions (only static fields).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

# Conf-key namespace kept string-compatible with the reference so users can
# port settings 1:1 (reference: mosaic/package.scala:21-43).
MOSAIC_INDEX_SYSTEM = "mosaic.index.system"
MOSAIC_GEOMETRY_API = "mosaic.geometry.api"
MOSAIC_RASTER_CHECKPOINT = "mosaic.raster.checkpoint"
MOSAIC_RASTER_USE_CHECKPOINT = "mosaic.raster.use.checkpoint"
MOSAIC_RASTER_TMP_PREFIX = "mosaic.raster.tmp.prefix"
MOSAIC_RASTER_BLOCKSIZE = "mosaic.raster.blocksize"
# Observability + CRS-strictness keys (no reference counterpart — the
# reference leans on the Spark UI; see mosaic_tpu/obs/).
MOSAIC_TRACE_ENABLED = "mosaic.trace.enabled"
MOSAIC_METRICS_ENABLED = "mosaic.metrics.enabled"
# Slow-query flight-recorder dump threshold in milliseconds; 0 (the
# default) disables the automatic dump (see mosaic_tpu/obs/recorder.py).
MOSAIC_OBS_SLOW_QUERY_MS = "mosaic.obs.slow.query.ms"
# Telemetry-sampler cadence in milliseconds (obs/timeseries.py): > 0
# starts a background thread snapshotting every registry metric into
# the bounded time-series store (and driving SLO evaluation + the
# per-device fold) on that cadence; 0 (the default) keeps it off.
# Env var MOSAIC_TPU_OBS_SAMPLE_MS pins the cadence over this key.
MOSAIC_OBS_SAMPLE_MS = "mosaic.obs.sample.ms"
# Write a flight-recorder dump bundle on every SLO breach transition
# (obs/slo.py); off by default — breaches always raise the recorder
# event + gauges regardless.
MOSAIC_OBS_SLO_DUMP = "mosaic.obs.slo.dump"
# Sampling host-profiler rate in Hz (obs/profiler.py): > 0 runs a
# daemon thread walking sys._current_frames() at that rate, folding
# samples into collapsed-stack counts with per-trace attribution; 0
# (the default) keeps it off.
# Env var MOSAIC_TPU_PROFILE_HZ pins the rate over this key.
MOSAIC_OBS_PROFILE_HZ = "mosaic.obs.profile.hz"
# Cooldown between AUTOMATIC flight-recorder dumps (slow-query and
# SLO-breach triggers share one gate — obs/recorder.py
# dump_throttled); dumps held by the gate raise a dump_suppressed
# event instead.  0 disables the gate (every trigger dumps).
MOSAIC_OBS_DUMP_COOLDOWN_MS = "mosaic.obs.dump.cooldown.ms"
# Bounded jax.profiler device-trace capture on triggered dumps
# (obs/profiler.py maybe_device_capture): > 0 records that many
# milliseconds of XLA timeline into the dump dir on each allowed
# auto-dump; 0 (default) disables the capture.
MOSAIC_OBS_PROFILE_TRACE_MS = "mosaic.obs.profile.trace.ms"
MOSAIC_CRS_STRICT_DATUM = "mosaic.crs.strict.datum"
# Precision-policy keys (fields existed since round 1; the conf spelling
# maps onto them so conf-driven deployments can set the policy too).
MOSAIC_DEVICE_DTYPE = "mosaic.device.dtype"
MOSAIC_EXACT_FALLBACK = "mosaic.exact.fallback"
# Ingestion error policy (see mosaic_tpu/resilience/ingest.py):
# "raise" fail-fast (default), "skip" drop malformed records, "null"
# null/zero-fill them — every codec threads this through.
MOSAIC_IO_ON_ERROR = "mosaic.io.on.error"
# Directory for JAX's persistent compilation cache (perf/jit_cache.py);
# empty (the default) keeps the checkout's .jax_cache/.  Env var
# JAX_COMPILATION_CACHE_DIR takes precedence over this key.
MOSAIC_JIT_CACHE_DIR = "mosaic.jit.cache.dir"
# Cadence (in consumed chunks) of the sharded streamed join's
# placement refresh (parallel/pip_join.py, parallel/placement.py):
# every K-th chunk re-packs the skew-aware placement from the
# matched-candidate density observed so far.
MOSAIC_SHARD_SKEW_REFRESH = "mosaic.shard.skew.refresh"
# Cost-based planner switches (sql/planner.py).  The planner is pure
# strategy selection — results are bit-for-bit identical either way —
# so `enabled` defaults on; force keys pin one operator's strategy
# ("mosaic.planner.force.knn" = "ring", say) for debugging
# or pathological workloads.
MOSAIC_PLANNER_ENABLED = "mosaic.planner.enabled"
MOSAIC_PLANNER_STATS_PATH = "mosaic.planner.stats.path"
MOSAIC_PLANNER_FORCE_PREFIX = "mosaic.planner.force."
# Streamed-executor chunk rows (parallel/pip_join.py double-buffered
# pipeline; previously a hard-coded 262_144 at every call site) and
# the KNN strategy ("auto" lets the planner choose brute vs. ring,
# "brute"/"ring" pin it, a positive integer overrides the
# brute-right-max row threshold; models/knn.py).
MOSAIC_STREAM_CHUNK_ROWS = "mosaic.stream.chunk.rows"
MOSAIC_KNN_STRATEGY = "mosaic.knn.strategy"
# Whole-query fusion (perf/fusion.py): compile adjacent eligible SQL
# operators into one XLA program per (group signature, size bucket).
# A pure strategy transform (bit-identical results), so `enabled`
# defaults on; the planner still gates each query per size class
# ("mosaic.planner.force.fusion" = on/off pins the gate).  `max.ops`
# caps group length — longer runs drop their earliest members.
MOSAIC_FUSION_ENABLED = "mosaic.fusion.enabled"
MOSAIC_FUSION_MAX_OPS = "mosaic.fusion.max.ops"
# Query accounting plane (obs/inflight.py + obs/accounting.py): the
# principal every query from this config is attributed to (session
# attribute `SQLSession.principal` overrides it; "" -> "anonymous"),
# a per-query cooperative deadline in milliseconds (0 disables; an
# expired deadline raises QueryCancelled at the next operator /
# chunk boundary), and the JSONL audit-spool path ("" keeps the
# audit log in-memory only).
MOSAIC_PRINCIPAL = "mosaic.principal"
MOSAIC_QUERY_DEADLINE_MS = "mosaic.query.deadline.ms"
MOSAIC_AUDIT_PATH = "mosaic.audit.path"
# Device-memory plane (obs/memwatch.py): a process budget in bytes for
# live device buffers (0 = unlimited; also the pressure denominator
# when smaller than the device capacity), the pressure fraction past
# which the streaming executor halves chunk rows (degrade-not-die),
# and the ledger master switch (default on; env MOSAIC_TPU_MEMWATCH=0
# pins it off for the bench overhead A/B).
MOSAIC_MEM_BUDGET_BYTES = "mosaic.mem.budget.bytes"
MOSAIC_MEM_PRESSURE_HIGH = "mosaic.mem.pressure.high"
MOSAIC_OBS_MEM_ENABLED = "mosaic.obs.mem.enabled"
# Multi-tenant query service (mosaic_tpu/serve/): listen port (0 =
# ephemeral, the test/loadtest default), worker-thread count, bounded
# admission-queue depth, per-principal quotas (concurrent queries
# queued+running, admissions/second over a 1s sliding window; 0
# disables either quota), a default per-request deadline (0 = none;
# the X-Mosaic-Deadline-Ms header overrides per request), the
# drain-on-SIGTERM grace period, and the micro-batcher's knobs: how
# long a worker waits for more compatible point lookups, the most
# queries one device launch may coalesce (0 disables batching — every
# query runs through SQLSession.sql), and the largest per-query row
# count still classified as batchable (sql/engine.classify_batchable).
MOSAIC_SERVE_PORT = "mosaic.serve.port"
MOSAIC_SERVE_WORKERS = "mosaic.serve.workers"
MOSAIC_SERVE_QUEUE_DEPTH = "mosaic.serve.queue.depth"
MOSAIC_SERVE_QUOTA_CONCURRENCY = "mosaic.serve.quota.concurrency"
MOSAIC_SERVE_QUOTA_QPS = "mosaic.serve.quota.qps"
MOSAIC_SERVE_DEADLINE_MS = "mosaic.serve.deadline.ms"
MOSAIC_SERVE_DRAIN_MS = "mosaic.serve.drain.ms"
MOSAIC_SERVE_BATCH_WINDOW_MS = "mosaic.serve.batch.window.ms"
MOSAIC_SERVE_BATCH_MAX = "mosaic.serve.batch.max"
MOSAIC_SERVE_BATCH_ROWS_MAX = "mosaic.serve.batch.rows.max"
# Supervised serving fleet (serve/supervisor.py + serve/scoreboard.py):
# worker-process count, the fleet runtime directory (ready files,
# scoreboard, supervisor.json; "" = a fresh temp dir per fleet), the
# crash-loop circuit breaker (more than `restart.max` respawns inside
# `restart.window.ms` parks the slot and the fleet runs degraded at
# N-1), the supervisor health-check cadence (0 disables the watchdog
# thread), how often dead workers' scoreboard claims are reaped (the
# under-admission bound), and the shared admission scoreboard's slot
# count (bounds fleet-wide queued+running + rate-window claims).
MOSAIC_SERVE_FLEET_WORKERS = "mosaic.serve.fleet.workers"
MOSAIC_SERVE_FLEET_DIR = "mosaic.serve.fleet.dir"
MOSAIC_SERVE_FLEET_RESTART_MAX = "mosaic.serve.fleet.restart.max"
MOSAIC_SERVE_FLEET_RESTART_WINDOW_MS = \
    "mosaic.serve.fleet.restart.window.ms"
MOSAIC_SERVE_FLEET_HEALTH_MS = "mosaic.serve.fleet.health.ms"
MOSAIC_SERVE_FLEET_REAP_MS = "mosaic.serve.fleet.reap.ms"
MOSAIC_SERVE_SCOREBOARD_SLOTS = "mosaic.serve.scoreboard.slots"
# Fleet telemetry plane (obs/spool.py + obs/fleet.py): the directory
# per-process telemetry spools are written to ("" disables spooling;
# writes ride the Sampler tick, so mosaic.obs.sample.ms must also be
# set for periodic snapshots), the spool-mtime age past which the
# aggregator flags a worker stale (its gauges drop out of the merged
# view; its counters/histograms stay — completed work doesn't
# un-happen), the raw-sample window each spool carries per series,
# and how many recent flight-recorder events ride in each snapshot
# (the fleet bundle and cross-process trace stitching read these).
MOSAIC_OBS_FLEET_DIR = "mosaic.obs.fleet.dir"
MOSAIC_OBS_FLEET_STALE_MS = "mosaic.obs.fleet.stale.ms"
MOSAIC_OBS_FLEET_WINDOW_MS = "mosaic.obs.fleet.window.ms"
MOSAIC_OBS_FLEET_EVENTS = "mosaic.obs.fleet.events"
# Out-of-core chip store (mosaic_tpu/store/): default root directory
# for grid-partitioned columnar stores ("" = no default; APIs take an
# explicit path), the fixed world-grid resolution new stores partition
# on (res x res cells over lon/lat — finer grids prune tighter but
# carry more partitions in the manifest), the target rows per shard
# file (a partition holding more rows splits into multiple shards so
# one read never materializes an unbounded column), and whether the
# reader memory-maps shard files (off copies each shard through a
# normal read — slower, but immune to mmap-unfriendly filesystems).
MOSAIC_STORE_DIR = "mosaic.store.dir"
MOSAIC_STORE_GRID_RES = "mosaic.store.grid.res"
MOSAIC_STORE_SHARD_ROWS = "mosaic.store.shard.rows"
MOSAIC_STORE_MMAP = "mosaic.store.mmap"

# Workload history plane (obs/history.py): a durable per-worker store
# of one record per completed query.  The directory ("" = history
# off), the rotation thresholds for the append-only open segment
# (bytes; age in ms, 0 = no age bound), the retained closed-segment
# cap, and the compaction window width in ms (records aggregate into
# one summary file per window).
MOSAIC_HISTORY_DIR = "mosaic.history.dir"
MOSAIC_HISTORY_SEGMENT_BYTES = "mosaic.history.segment.bytes"
MOSAIC_HISTORY_SEGMENT_AGE_MS = "mosaic.history.segment.age.ms"
MOSAIC_HISTORY_RETAIN = "mosaic.history.retain"
MOSAIC_HISTORY_WINDOW_MS = "mosaic.history.window.ms"
# Partition heat (obs/heat.py): the exponential half-life of the
# per-cell access accumulators (0 = never decay), and whether the
# store-fed join hands the accumulated heat to the skew rebalancer as
# a placement prior (a pure hint — results stay bit-identical).
MOSAIC_HEAT_HALFLIFE_MS = "mosaic.heat.halflife.ms"
MOSAIC_HEAT_PRIOR = "mosaic.heat.prior"
# Adaptive PIP refinement (parallel/pip_join.py): per-cell second-level
# tessellation of the dense border cells only.  A pure strategy
# transform — bit-identical to the flat single-level join.  `enabled`
# is the kill switch (beats any planner pin), `depth` the extra levels
# the dense cells deepen by, `dup.threshold` the per-cell candidate
# count below which a cell never refines, `max.cells` the cap on the
# refined set, and `sample.rows` how many leading rows feed the
# selectivity probe that picks the dense cells.
MOSAIC_JOIN_REFINE_ENABLED = "mosaic.join.refine.enabled"
MOSAIC_JOIN_REFINE_DEPTH = "mosaic.join.refine.depth"
MOSAIC_JOIN_REFINE_DUP_THRESHOLD = "mosaic.join.refine.dup.threshold"
MOSAIC_JOIN_REFINE_MAX_CELLS = "mosaic.join.refine.max.cells"
MOSAIC_JOIN_REFINE_SAMPLE_ROWS = "mosaic.join.refine.sample.rows"
# Learned layout advisor (sql/layout.py): target occupied-cell row
# count the advisor sizes ``store.grid.res`` for, and the inclusive
# resolution clamp it never strays outside of.
MOSAIC_LAYOUT_ROWS_PER_CELL = "mosaic.layout.rows.per.cell"
MOSAIC_LAYOUT_MIN_RES = "mosaic.layout.min.res"
MOSAIC_LAYOUT_MAX_RES = "mosaic.layout.max.res"
# Audit-spool bounds (obs/accounting.py): rotate the JSONL spool past
# this size (0 = unbounded, the historical behaviour) and keep at
# most this many rotated files.
MOSAIC_AUDIT_ROTATE_BYTES = "mosaic.audit.rotate.bytes"
MOSAIC_AUDIT_RETAIN = "mosaic.audit.retain"

MOSAIC_RASTER_CHECKPOINT_DEFAULT = "/tmp/mosaic_tpu/checkpoint"
MOSAIC_RASTER_TMP_PREFIX_DEFAULT = "/tmp"
MOSAIC_RASTER_BLOCKSIZE_DEFAULT = 128


class ConfigError(ValueError):
    """A conf key carried an unusable value; the message names the key."""


@dataclasses.dataclass(frozen=True)
class MosaicConfig:
    """Immutable snapshot of framework settings.

    Mirrors MosaicExpressionConfig: the (index system, geometry backend)
    pair plus raster checkpoint behaviour travels with every operation so
    compute code never consults global mutable state.
    """

    index_system: str = "H3"          # "H3" | "BNG" | "CUSTOM(...)"
    geometry_api: str = "JAX"         # device-vectorized backend (only impl)
    raster_checkpoint: str = MOSAIC_RASTER_CHECKPOINT_DEFAULT
    raster_use_checkpoint: bool = False
    raster_tmp_prefix: str = MOSAIC_RASTER_TMP_PREFIX_DEFAULT
    raster_blocksize: int = MOSAIC_RASTER_BLOCKSIZE_DEFAULT
    # Device-compute precision policy.  Cell assignment / PIP run in f32 on
    # TPU with an epsilon "uncertainty band"; points inside the band are
    # re-checked in f64 on host so results match the host reference exactly
    # (design note: DESIGN.md §precision).
    device_dtype: str = "float32"
    exact_fallback: bool = True
    # Observability switches (see mosaic_tpu/obs/): span tracer and
    # metrics registry.  Env vars MOSAIC_TPU_TRACE / MOSAIC_TPU_METRICS
    # override these to on; conf keys only ever turn instruments on.
    trace_enabled: bool = False
    metrics_enabled: bool = False
    # SQLSession.sql() calls slower than this many milliseconds trigger
    # an automatic flight-recorder dump; 0 disables the trigger.
    obs_slow_query_ms: float = 0.0
    # Telemetry-sampler cadence (ms): registry -> time-series store
    # snapshots + SLO evaluation + per-device fold run on a background
    # thread at this interval.  0 (default) = no sampler thread.
    obs_sample_ms: float = 0.0
    # Dump a flight bundle whenever an SLO objective newly breaches.
    obs_slo_dump: bool = False
    # Sampling host-profiler rate (Hz); 0 (default) = no profiler
    # thread.
    obs_profile_hz: float = 0.0
    # Minimum spacing between automatic dump-bundle writes (slow-query
    # + SLO triggers share the gate); 0 disables the cooldown.
    obs_dump_cooldown_ms: float = 30_000.0
    # Bounded device-profiler capture on triggered dumps (ms of
    # jax.profiler timeline); 0 disables.
    obs_profile_trace_ms: float = 0.0
    # Raise (instead of warn) when a CRS transform would silently apply
    # an identity datum shift because the EPSG registry carries no
    # Helmert parameters for the code (helmert_acc is NaN).
    crs_strict_datum: bool = False
    # Codec error policy (resilience/ingest.py): what a malformed
    # record/strip/message does — fail fast, get dropped, or get nulled.
    io_on_error: str = "raise"
    # On-disk compiled-kernel cache directory; "" keeps the checkout's
    # .jax_cache/.  JAX_COMPILATION_CACHE_DIR, when set, wins over it.
    # Warm-started processes load XLA executables from disk instead of
    # recompiling.
    jit_cache_dir: str = ""
    # Every K-th chunk of a sharded streamed join re-packs the
    # skew-aware placement.  Smaller = fresher placement, more
    # re-packs.
    shard_skew_refresh: int = 16
    # Cost-based planner (sql/planner.py): per-query strategy choice
    # from observed stats.  Pure strategy transform — turning it off
    # changes speed, never results.
    planner_enabled: bool = True
    # Persisted learned-coefficient file; "" keeps stats in-process
    # only.  Env var MOSAIC_TPU_PLANNER_STATS takes precedence.
    planner_stats_path: str = ""
    # ((op, strategy), ...) pins from mosaic.planner.force.<op> keys;
    # ops/strategies validated against planner.FORCE_CHOICES.
    planner_force: tuple = ()
    # Rows per streamed-executor chunk (double-buffered device
    # pipeline).
    stream_chunk_rows: int = 262_144
    # "auto" | "brute" | "ring" | positive-int brute-right-max.
    knn_strategy: str = "auto"
    # Whole-query fusion master switch (perf/fusion.py).  Off = every
    # operator dispatches separately, as before the fusion pass.
    fusion_enabled: bool = True
    # Fusion group-size cap (member operators per compiled group).
    fusion_max_ops: int = 8
    # Principal queries under this config are metered as ("" falls
    # back to "anonymous"; SQLSession.principal overrides per session).
    principal: str = ""
    # Cooperative per-query deadline (ms): a query past it raises
    # QueryCancelled at its next checkpoint.  0 = no deadline.
    query_deadline_ms: float = 0.0
    # JSONL audit-spool path for query completion records; "" keeps
    # the audit log in-memory only (bounded ring).
    audit_path: str = ""
    # Live device-memory budget in bytes (obs/memwatch.py); 0 = no
    # budget (pressure is measured against device capacity only).
    mem_budget_bytes: int = 0
    # Fraction of the effective capacity past which the streamed
    # executor halves its next chunk (mem/chunk_shrink counter).
    mem_pressure_high: float = 0.85
    # Device-memory ledger master switch (register/release tracking,
    # per-query attribution, leak sentinel).
    obs_mem_enabled: bool = True
    # Query service (mosaic_tpu/serve/) — see the mosaic.serve.* key
    # comments above for semantics.
    serve_port: int = 0
    serve_workers: int = 4
    serve_queue_depth: int = 64
    serve_quota_concurrency: int = 8
    serve_quota_qps: float = 0.0
    serve_deadline_ms: float = 0.0
    serve_drain_ms: float = 5_000.0
    serve_batch_window_ms: float = 2.0
    serve_batch_max: int = 32
    serve_batch_rows_max: int = 4_096
    # Supervised serving fleet — see the mosaic.serve.fleet.* key
    # comments above.
    serve_fleet_workers: int = 2
    serve_fleet_dir: str = ""
    serve_fleet_restart_max: int = 5
    serve_fleet_restart_window_ms: float = 30_000.0
    serve_fleet_health_ms: float = 250.0
    serve_fleet_reap_ms: float = 1_000.0
    serve_scoreboard_slots: int = 512
    # Fleet telemetry plane — see the mosaic.obs.fleet.* key comments
    # above.  "" = no spooling.
    obs_fleet_dir: str = ""
    obs_fleet_stale_ms: float = 5_000.0
    obs_fleet_window_ms: float = 300_000.0
    obs_fleet_events: int = 512
    # Out-of-core chip store — see the mosaic.store.* key comments
    # above.  "" = no default store directory.
    store_dir: str = ""
    store_grid_res: int = 1_024
    store_shard_rows: int = 4_194_304
    store_mmap: bool = True
    # Workload history plane (obs/history.py); "" = history off.
    history_dir: str = ""
    history_segment_bytes: int = 1_048_576
    history_segment_age_ms: float = 0.0
    history_retain: int = 64
    history_window_ms: float = 3_600_000.0
    # Partition heat (obs/heat.py): accumulator half-life (0 = never
    # decay) and the opt-in placement prior for the skew rebalancer.
    heat_halflife_ms: float = 300_000.0
    heat_prior: bool = False
    # Adaptive PIP refinement — see the mosaic.join.refine.* key
    # comments above.  Bit-identical either way; `enabled` off beats
    # any planner pin.
    join_refine_enabled: bool = True
    join_refine_depth: int = 1
    join_refine_dup_threshold: int = 8
    join_refine_max_cells: int = 4_096
    join_refine_sample_rows: int = 65_536
    # Learned layout advisor (sql/layout.py) — see mosaic.layout.*.
    layout_rows_per_cell: int = 65_536
    layout_min_res: int = 64
    layout_max_res: int = 16_384
    # Audit-spool bounds; rotate_bytes 0 = unbounded spool.
    audit_rotate_bytes: int = 0
    audit_retain: int = 8

    @staticmethod
    def from_confs(confs: dict) -> "MosaicConfig":
        """Build from a reference-style string conf map.

        Every known key is validated — a bad value raises
        :class:`ConfigError` naming the key; unknown keys are ignored
        (reference behaviour: Spark confs are an open namespace)."""
        cfg = MosaicConfig()
        for key in confs:
            if key in _CONF_FIELDS or \
                    key.startswith(MOSAIC_PLANNER_FORCE_PREFIX):
                cfg = apply_conf(cfg, key, confs[key])
        return cfg


# ------------------------------------------------ conf-key validation

def _as_flag(key: str, value) -> bool:
    s = str(value).strip().lower()
    if s in ("true", "1", "yes", "on"):
        return True
    if s in ("false", "0", "no", "off"):
        return False
    raise ConfigError(f"{key}={value!r} is not a boolean "
                      "(use true/false)")


def _as_blocksize(key: str, value) -> int:
    try:
        n = int(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not an integer") from None
    if n <= 0:
        raise ConfigError(f"{key}={n} must be a positive integer")
    return n


def _as_device_dtype(key: str, value) -> str:
    s = str(value).strip().lower()
    if s not in ("float32", "float64"):
        raise ConfigError(f"{key}={value!r} unsupported "
                          "(float32 or float64)")
    return s


def _as_on_error(key: str, value) -> str:
    s = str(value).strip().lower()
    if s not in ("raise", "skip", "null"):
        raise ConfigError(f"{key}={value!r} invalid "
                          "(raise, skip, or null)")
    return s


def _as_millis(key: str, value) -> float:
    try:
        ms = float(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not a number of milliseconds") from None
    if ms < 0:
        raise ConfigError(f"{key}={ms} must be >= 0 (0 disables)")
    return ms


def _as_hz(key: str, value) -> float:
    try:
        hz = float(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not a rate in Hz") from None
    if hz < 0:
        raise ConfigError(f"{key}={hz} must be >= 0 (0 disables)")
    return hz


def _as_bytes(key: str, value) -> int:
    try:
        n = int(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not a byte count") from None
    if n < 0:
        raise ConfigError(f"{key}={n} must be >= 0 (0 = unlimited)")
    return n


def _as_fraction(key: str, value) -> float:
    try:
        f = float(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not a fraction") from None
    if not 0.0 < f <= 1.0:
        raise ConfigError(f"{key}={f} must be in (0, 1]")
    return f


def _as_str(key: str, value) -> str:
    return str(value)


def _as_count(key: str, value) -> int:
    try:
        n = int(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not an integer") from None
    if n < 0:
        raise ConfigError(f"{key}={n} must be >= 0 (0 disables)")
    return n


def _as_port(key: str, value) -> int:
    try:
        n = int(str(value).strip())
    except (TypeError, ValueError):
        raise ConfigError(
            f"{key}={value!r} is not a port number") from None
    if not 0 <= n <= 65535:
        raise ConfigError(f"{key}={n} must be in [0, 65535] "
                          "(0 = ephemeral)")
    return n


def _as_knn_strategy(key: str, value) -> str:
    s = str(value).strip().lower()
    if s in ("auto", "brute", "ring"):
        return s
    try:
        n = int(s)
    except ValueError:
        raise ConfigError(
            f"{key}={value!r} invalid (auto, brute, ring, or a "
            "positive integer brute-right-max threshold)") from None
    if n <= 0:
        raise ConfigError(f"{key}={n} threshold must be positive")
    return str(n)


#: conf key -> (dataclass field, validating coercer)
_CONF_FIELDS = {
    MOSAIC_INDEX_SYSTEM: ("index_system", _as_str),
    MOSAIC_GEOMETRY_API: ("geometry_api", _as_str),
    MOSAIC_RASTER_CHECKPOINT: ("raster_checkpoint", _as_str),
    MOSAIC_RASTER_USE_CHECKPOINT: ("raster_use_checkpoint", _as_flag),
    MOSAIC_RASTER_TMP_PREFIX: ("raster_tmp_prefix", _as_str),
    MOSAIC_RASTER_BLOCKSIZE: ("raster_blocksize", _as_blocksize),
    MOSAIC_DEVICE_DTYPE: ("device_dtype", _as_device_dtype),
    MOSAIC_EXACT_FALLBACK: ("exact_fallback", _as_flag),
    MOSAIC_TRACE_ENABLED: ("trace_enabled", _as_flag),
    MOSAIC_METRICS_ENABLED: ("metrics_enabled", _as_flag),
    MOSAIC_OBS_SLOW_QUERY_MS: ("obs_slow_query_ms", _as_millis),
    MOSAIC_OBS_SAMPLE_MS: ("obs_sample_ms", _as_millis),
    MOSAIC_OBS_SLO_DUMP: ("obs_slo_dump", _as_flag),
    MOSAIC_OBS_PROFILE_HZ: ("obs_profile_hz", _as_hz),
    MOSAIC_OBS_DUMP_COOLDOWN_MS: ("obs_dump_cooldown_ms", _as_millis),
    MOSAIC_OBS_PROFILE_TRACE_MS: ("obs_profile_trace_ms", _as_millis),
    MOSAIC_CRS_STRICT_DATUM: ("crs_strict_datum", _as_flag),
    MOSAIC_IO_ON_ERROR: ("io_on_error", _as_on_error),
    MOSAIC_JIT_CACHE_DIR: ("jit_cache_dir", _as_str),
    MOSAIC_SHARD_SKEW_REFRESH: ("shard_skew_refresh", _as_blocksize),
    MOSAIC_PLANNER_ENABLED: ("planner_enabled", _as_flag),
    MOSAIC_PLANNER_STATS_PATH: ("planner_stats_path", _as_str),
    MOSAIC_STREAM_CHUNK_ROWS: ("stream_chunk_rows", _as_blocksize),
    MOSAIC_KNN_STRATEGY: ("knn_strategy", _as_knn_strategy),
    MOSAIC_FUSION_ENABLED: ("fusion_enabled", _as_flag),
    MOSAIC_FUSION_MAX_OPS: ("fusion_max_ops", _as_blocksize),
    MOSAIC_PRINCIPAL: ("principal", _as_str),
    MOSAIC_QUERY_DEADLINE_MS: ("query_deadline_ms", _as_millis),
    MOSAIC_AUDIT_PATH: ("audit_path", _as_str),
    MOSAIC_MEM_BUDGET_BYTES: ("mem_budget_bytes", _as_bytes),
    MOSAIC_MEM_PRESSURE_HIGH: ("mem_pressure_high", _as_fraction),
    MOSAIC_OBS_MEM_ENABLED: ("obs_mem_enabled", _as_flag),
    MOSAIC_SERVE_PORT: ("serve_port", _as_port),
    MOSAIC_SERVE_WORKERS: ("serve_workers", _as_blocksize),
    MOSAIC_SERVE_QUEUE_DEPTH: ("serve_queue_depth", _as_blocksize),
    MOSAIC_SERVE_QUOTA_CONCURRENCY: ("serve_quota_concurrency",
                                     _as_count),
    MOSAIC_SERVE_QUOTA_QPS: ("serve_quota_qps", _as_hz),
    MOSAIC_SERVE_DEADLINE_MS: ("serve_deadline_ms", _as_millis),
    MOSAIC_SERVE_DRAIN_MS: ("serve_drain_ms", _as_millis),
    MOSAIC_SERVE_BATCH_WINDOW_MS: ("serve_batch_window_ms", _as_millis),
    MOSAIC_SERVE_BATCH_MAX: ("serve_batch_max", _as_count),
    MOSAIC_SERVE_BATCH_ROWS_MAX: ("serve_batch_rows_max",
                                  _as_blocksize),
    MOSAIC_SERVE_FLEET_WORKERS: ("serve_fleet_workers", _as_blocksize),
    MOSAIC_SERVE_FLEET_DIR: ("serve_fleet_dir", _as_str),
    MOSAIC_SERVE_FLEET_RESTART_MAX: ("serve_fleet_restart_max",
                                     _as_blocksize),
    MOSAIC_SERVE_FLEET_RESTART_WINDOW_MS:
        ("serve_fleet_restart_window_ms", _as_millis),
    MOSAIC_SERVE_FLEET_HEALTH_MS: ("serve_fleet_health_ms",
                                   _as_millis),
    MOSAIC_SERVE_FLEET_REAP_MS: ("serve_fleet_reap_ms", _as_millis),
    MOSAIC_SERVE_SCOREBOARD_SLOTS: ("serve_scoreboard_slots",
                                    _as_blocksize),
    MOSAIC_OBS_FLEET_DIR: ("obs_fleet_dir", _as_str),
    MOSAIC_OBS_FLEET_STALE_MS: ("obs_fleet_stale_ms", _as_millis),
    MOSAIC_OBS_FLEET_WINDOW_MS: ("obs_fleet_window_ms", _as_millis),
    MOSAIC_OBS_FLEET_EVENTS: ("obs_fleet_events", _as_count),
    MOSAIC_STORE_DIR: ("store_dir", _as_str),
    MOSAIC_STORE_GRID_RES: ("store_grid_res", _as_blocksize),
    MOSAIC_STORE_SHARD_ROWS: ("store_shard_rows", _as_blocksize),
    MOSAIC_STORE_MMAP: ("store_mmap", _as_flag),
    MOSAIC_HISTORY_DIR: ("history_dir", _as_str),
    MOSAIC_HISTORY_SEGMENT_BYTES: ("history_segment_bytes", _as_blocksize),
    MOSAIC_HISTORY_SEGMENT_AGE_MS: ("history_segment_age_ms", _as_millis),
    MOSAIC_HISTORY_RETAIN: ("history_retain", _as_count),
    MOSAIC_HISTORY_WINDOW_MS: ("history_window_ms", _as_millis),
    MOSAIC_HEAT_HALFLIFE_MS: ("heat_halflife_ms", _as_millis),
    MOSAIC_HEAT_PRIOR: ("heat_prior", _as_flag),
    MOSAIC_JOIN_REFINE_ENABLED: ("join_refine_enabled", _as_flag),
    MOSAIC_JOIN_REFINE_DEPTH: ("join_refine_depth", _as_blocksize),
    MOSAIC_JOIN_REFINE_DUP_THRESHOLD:
        ("join_refine_dup_threshold", _as_count),
    MOSAIC_JOIN_REFINE_MAX_CELLS:
        ("join_refine_max_cells", _as_blocksize),
    MOSAIC_JOIN_REFINE_SAMPLE_ROWS:
        ("join_refine_sample_rows", _as_blocksize),
    MOSAIC_LAYOUT_ROWS_PER_CELL:
        ("layout_rows_per_cell", _as_blocksize),
    MOSAIC_LAYOUT_MIN_RES: ("layout_min_res", _as_blocksize),
    MOSAIC_LAYOUT_MAX_RES: ("layout_max_res", _as_blocksize),
    MOSAIC_AUDIT_ROTATE_BYTES: ("audit_rotate_bytes", _as_bytes),
    MOSAIC_AUDIT_RETAIN: ("audit_retain", _as_count),
}


def _apply_planner_force(cfg: MosaicConfig, key: str,
                         value) -> MosaicConfig:
    """``mosaic.planner.force.<op>`` assignment: validate op and
    strategy against the planner's registry, "auto" clears the pin."""
    from .sql.planner import FORCE_CHOICES
    op = key[len(MOSAIC_PLANNER_FORCE_PREFIX):]
    if op not in FORCE_CHOICES:
        raise ConfigError(
            f"{key!r}: unknown plannable op {op!r} (known: "
            f"{', '.join(sorted(FORCE_CHOICES))})")
    s = str(value).strip().lower()
    if s not in FORCE_CHOICES[op]:
        raise ConfigError(
            f"{key}={value!r} invalid "
            f"({', '.join(FORCE_CHOICES[op])})")
    force = tuple((o, st) for o, st in cfg.planner_force if o != op)
    if s != "auto":
        force = force + ((op, s),)
    return dataclasses.replace(cfg, planner_force=force)


def planner_force_for(cfg: MosaicConfig, op: str) -> str:
    """The pinned strategy for ``op`` ("auto" when unpinned)."""
    for o, s in getattr(cfg, "planner_force", ()):
        if o == op:
            return s
    return "auto"


def apply_conf(cfg: MosaicConfig, key: str, value) -> MosaicConfig:
    """One validated conf assignment -> a new config.

    Unlike :meth:`MosaicConfig.from_confs` (open namespace), a key this
    build does not know raises — this is the ``SET`` statement /
    programmatic path where a typo should not vanish silently."""
    if key.startswith(MOSAIC_PLANNER_FORCE_PREFIX):
        new = _apply_planner_force(cfg, key, value)
        from .obs.recorder import recorder
        recorder.record("config", key=key, value=str(value))
        return new
    if key not in _CONF_FIELDS:
        raise ConfigError(
            f"unknown conf key {key!r} (known: "
            f"{', '.join(sorted(_CONF_FIELDS))} and "
            f"{MOSAIC_PLANNER_FORCE_PREFIX}<op>)")
    field, coerce = _CONF_FIELDS[key]
    coerced = coerce(key, value)
    # config mutations are flight-recorder events: a post-mortem bundle
    # shows which SET preceded the failure (lazy import — obs imports
    # this module back for bundle snapshots)
    from .obs.recorder import recorder
    recorder.record("config", key=key, value=str(value))
    return dataclasses.replace(cfg, **{field: coerced})


_default_config: MosaicConfig = MosaicConfig()


def set_default_config(cfg: MosaicConfig) -> None:
    global _default_config
    _default_config = cfg
    # Conf-driven observability enablement (one-way: never disables an
    # instrument the env or an explicit enable() already turned on).
    # The sampler cadence routes through here too (change-detecting,
    # env-pinned-safe — see obs.timeseries.configure_sampler).
    if cfg.trace_enabled or cfg.metrics_enabled or cfg.obs_sample_ms \
            or cfg.obs_profile_hz:
        from .obs import configure
        configure(cfg)
    else:
        from .obs.timeseries import configure_sampler
        configure_sampler(0.0)
        from .obs.profiler import configure_profiler
        configure_profiler(0.0)
    if cfg.jit_cache_dir:
        from .perf.jit_cache import configure_persistent_cache
        configure_persistent_cache(cfg.jit_cache_dir)
    if cfg.planner_stats_path:
        from .sql.planner import planner
        planner.configure_stats(cfg.planner_stats_path)


def default_config() -> MosaicConfig:
    return _default_config
