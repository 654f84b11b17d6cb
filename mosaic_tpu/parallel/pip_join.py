"""The index-accelerated point-in-polygon join — the flagship pipeline.

Reference counterpart: the Quickstart workload
(notebooks/examples/python/Quickstart/QuickstartNotebook.ipynb): points get
``grid_pointascellid``, polygons get ``grid_tessellateexplode``, Spark
equi-joins on cell id, then filters ``is_core OR st_contains(chip, point)``.

TPU-first redesign: the tessellated polygon side becomes a device-resident
sorted cell-id table (core cells + border cells with padded chip edge
blocks).  The per-point pipeline is one fused XLA computation:

    cell   = grid.point_to_cell_jax(points)          # closed-form bit math
    islot  = binary-search cell in core/border table # ops.lookup
    inside = crossing-parity vs the <=D chips in the cell
    zone   = core hit ? core zone : first chip hit

No shuffle is needed while the polygon side fits in HBM (the reference's
broadcast-join regime; ~300 taxi zones → a few MB of chips).  Points shard
over the mesh's data axis via jax.sharding; the table replicates.  For
polygon×polygon joins both sides shard — see overlay.py (cell-bucketed
all_to_all).

Precision: device compute is float32; points whose distance to a chip
boundary is below ``eps`` are flagged and re-checked on host in float64
against the same chips, so results match the exact host path
(config.MosaicConfig.exact_fallback).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.geometry.array import GeometryArray
from ..core.geometry.padded import build_edges
from ..core.index.base import IndexSystem
from ..core.tessellate import tessellate
from ..ops.lookup import lookup
from ..perf.pipeline import chunk_rows, stream
from ..types import ChipSet

#: f32 hazard band (degrees) around chip edges for the crossing-parity
#: test: covers the f32 representation of points and chip vertices
#: (~1.5e-8 deg at city magnitudes) and the f32 edge-intersection
#: arithmetic (~1e-7 deg), with ~8x safety.
EPS_EDGE_DEG = 1e-6


def _workload_origin(polys: GeometryArray) -> np.ndarray:
    """Shared local-frame origin of a polygon batch: round(mean bbox).
    Both index types use this, so localize() inputs are interchangeable
    between them for the same polygons."""
    bb = polys.bboxes()
    return np.round(np.array(
        [np.nanmean(bb[:, [0, 2]]), np.nanmean(bb[:, [1, 3]])]), 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PIPIndex:
    """Device-resident tessellation index of a polygon batch.

    core_cells   [C]        sorted cell ids fully inside some polygon
    core_zone    [C]        polygon id per core cell
    border_cells [B]        sorted cell ids on some polygon's boundary
                            (duplicates allowed: one entry per chip)
    border_zone  [B]        polygon id per chip
    chip_a/b     [B, E, 2]  chip edges (float32)
    chip_mask    [B, E]
    max_dup      static     max chips sharing one cell id (probe width)
    res          static     grid resolution
    """

    core_cells: jnp.ndarray
    core_zone: jnp.ndarray
    border_cells: jnp.ndarray
    border_zone: jnp.ndarray
    chip_a: jnp.ndarray
    chip_b: jnp.ndarray
    chip_mask: jnp.ndarray
    #: local-frame origin (lon, lat float64): chip coords are stored
    #: origin-shifted so float32 edge-crossing arithmetic operates on
    #: small magnitudes (absolute lon ~74° costs ~4e-5° of cancellation
    #: error — far above the eps band; shifted it is ~1e-7°)
    origin: jnp.ndarray
    max_dup: int
    res: int
    #: exact max chord-vs-gnomonic cell-edge deviation (planar degrees)
    #: over THIS index's cells — the extra cell-assignment uncertainty
    #: band the join must honor (see cells_edge_sagitta_deg)
    sagitta_deg: float = 0.0

    def tree_flatten(self):
        return ((self.core_cells, self.core_zone, self.border_cells,
                 self.border_zone, self.chip_a, self.chip_b,
                 self.chip_mask, self.origin),
                (self.max_dup, self.res, self.sagitta_deg))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def num_chips(self) -> int:
        return self.border_cells.shape[0]


def build_pip_index(polys: GeometryArray, res: int, grid: IndexSystem,
                    chips: Optional[ChipSet] = None,
                    dtype=jnp.float32, dense: str = "auto"):
    """Tessellate polygons and lay the chips out for device lookup.

    Returns a DensePIPIndex (one-gather lattice-window fast path) when
    the workload allows it, else the grid-agnostic sorted-table
    PIPIndex.  ``dense``: "auto" | "never" | "require".

    Float32 cell-assignment hazards need no special index structure: the
    device quantizer reports a boundary margin, and low-margin points are
    flagged for the float64 host recheck (see make_pip_join_fn)."""
    if chips is None:
        chips = tessellate(polys, res, grid, keep_core_geom=False)
    if dense != "never":
        d = build_dense_pip_index(polys, res, grid, chips=chips)
        if d is not None:
            return d
        if dense == "require":
            raise ValueError("workload does not fit the dense fast path")
    origin = _workload_origin(polys)
    core = chips.is_core
    core_cells = chips.cell_id[core]
    core_zone = chips.geom_id[core]
    order = np.argsort(core_cells, kind="stable")
    core_cells, core_zone = core_cells[order], core_zone[order]

    b_cells = chips.cell_id[~core]
    b_zone = chips.geom_id[~core]
    border_idx = np.nonzero(~core)[0]
    order = np.argsort(b_cells, kind="stable")
    b_cells, b_zone = b_cells[order], b_zone[order]
    if len(b_cells):
        _, counts = np.unique(b_cells, return_counts=True)
        max_dup = int(counts.max())
    else:
        max_dup = 1
    if len(b_cells):
        chip_geoms = chips.geoms.take(border_idx[order])
        chip_geoms.coords = chip_geoms.coords - origin[None, :2]
    else:
        chip_geoms = GeometryArray.empty()
    e = build_edges(chip_geoms, dtype=dtype) if len(b_cells) else None
    if e is None:
        cap = 8
        a = jnp.zeros((0, cap, 2), dtype)
        b = jnp.zeros((0, cap, 2), dtype)
        m = jnp.zeros((0, cap), bool)
    else:
        a, b, m = e.a, e.b, e.mask
    return PIPIndex(
        core_cells=jnp.asarray(core_cells), core_zone=jnp.asarray(
            core_zone.astype(np.int32)),
        border_cells=jnp.asarray(b_cells), border_zone=jnp.asarray(
            b_zone.astype(np.int32)),
        chip_a=a, chip_b=b, chip_mask=m,
        origin=jnp.asarray(origin, jnp.float64),
        max_dup=max_dup, res=res,
        sagitta_deg=(grid.cells_edge_sagitta_deg(
            np.unique(chips.cell_id)) if hasattr(
                grid, "cells_edge_sagitta_deg") else 0.0))


# ------------------------------------------------------------ device side

def _chip_pip(points: jnp.ndarray, idx: PIPIndex,
              slots: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Crossing-parity containment of each point in the chip at its slot.

    points [N, 2], slots [N] int32 -> (inside [N] bool, min boundary
    distance² [N]).  One gather of that chip's edges per point; the [N, E]
    broadcast is the hot inner loop of the whole join.
    """
    a = idx.chip_a[slots]           # [N, E, 2]
    b = idx.chip_b[slots]
    mask = idx.chip_mask[slots]
    px = points[:, None, 0]
    py = points[:, None, 1]
    ax, ay = a[..., 0], a[..., 1]
    bx, by = b[..., 0], b[..., 1]
    straddle = (ay <= py) != (by <= py)
    t = (py - ay) / jnp.where(by == ay, jnp.ones_like(by), by - ay)
    xi = ax + t * (bx - ax)
    hits = straddle & (px < xi) & mask
    inside = (jnp.sum(hits, axis=-1) & 1).astype(bool)
    # boundary distance² for the exact-fallback band
    ab = b - a
    ap = points[:, None, :] - a
    denom = jnp.sum(ab * ab, axis=-1)
    tt = jnp.clip(jnp.sum(ap * ab, axis=-1) / jnp.where(denom == 0,
                                                        1.0, denom), 0., 1.)
    proj = a + tt[..., None] * ab
    d = points[:, None, :] - proj
    d2 = jnp.where(mask, jnp.sum(d * d, axis=-1), jnp.inf)
    return inside, jnp.min(d2, axis=-1)


def pip_assign(points: jnp.ndarray, cells: jnp.ndarray, idx: PIPIndex,
               eps: float = 1e-5) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assign each point to a polygon id (or -1).

    points [N, 2] (grid CRS), cells [N] int64 (precomputed cell per point).
    Returns (zone [N] int32, uncertain [N] bool).  ``uncertain`` marks
    points within eps of a chip boundary — the float64 host recheck set.
    """
    n = points.shape[0]
    # size-0 tables are legal (a workload can tessellate to border-only
    # chips, or — under adaptive refinement — a sub-level can come out
    # core-only); lookup() already returns found=False there, but the
    # zone/edge gathers need static guards too.
    if idx.core_cells.shape[0]:
        slot, in_core = lookup(idx.core_cells, cells)
        zone = jnp.where(in_core, idx.core_zone[slot], jnp.int32(-1))
    else:
        zone = jnp.full(n, -1, jnp.int32)

    b0, in_border = lookup(idx.border_cells, cells)
    uncertain = jnp.zeros(n, bool)
    for d in range(idx.max_dup if idx.num_chips else 0):
        s = jnp.clip(b0 + d, 0, max(idx.num_chips - 1, 0))
        valid = in_border & (idx.border_cells[s] == cells) & \
            (b0 + d < max(idx.num_chips, 1))
        inside, d2 = _chip_pip(points, idx, s)
        hit = valid & inside & (zone < 0)
        zone = jnp.where(hit, idx.border_zone[s], zone)
        uncertain |= valid & (d2 < eps * eps)
    return zone, uncertain


def localize(idx: PIPIndex, points64: np.ndarray) -> np.ndarray:
    """Absolute float64 points -> local-frame float32 device input.

    The origin shift happens in float64 BEFORE the float32 cast, so the
    device sees full point precision in the frame the chips live in."""
    return np.asarray(points64 - np.asarray(idx.origin)[None],
                      np.float32)


def make_pip_join_fn(idx, grid: IndexSystem, eps: Optional[float] = None,
                     margin_eps: Optional[float] = None,
                     precision: str = "auto"):
    """Close the index over a jitted ``local_points -> (zone,
    uncertain)``; inputs come from ``localize`` (local-frame float32).
    Dense indexes dispatch to make_dense_pip_join_fn; the index's
    tables are arguments of the executable, also inside a caller's own
    jit (one that shards the points, say).

    ``precision`` pins the dense path's projection arithmetic ("f32" /
    "df" / "f64"; see ``h3.jaxkernel.pick_precision``).  "auto" resolves
    per backend — note it picks native f64 on CPU whenever
    ``jax_enable_x64`` is on, which is exact-but-slow; throughput
    benchmarks that enable x64 for other subsystems should pin the
    arithmetic they mean to measure.  Exactness does not depend on the
    choice: wider-error paths raise ``uncertain`` over a wider margin
    band and the f64 host recheck resolves them.

    Exactness contract: every float32 hazard raises ``uncertain``, and
    host_recheck resolves those in float64 — (a) points within ``eps`` of
    a chip boundary (crossing-parity rounding), (b) points whose
    cell-boundary margin is below ``margin_eps`` (cell assignment could
    differ from the float64 path: local→absolute rounding ~4e-6° plus
    f32 projection error), (c) points near the grid's domain edge.
    Out-of-domain points are forced to zone −1."""
    if isinstance(idx, DensePIPIndex):
        # the index is an argument of the executable, not a constant in
        # it: a cell-keyed table is tens of MB, and as a constant it
        # would be compiled, cached and loaded anew with every program
        join = jax.jit(make_dense_pip_join_fn(
            idx, eps=EPS_EDGE_DEG if eps is None else eps,
            precision=precision, margin_eps_deg=margin_eps))

        def pip_dense_join(points):
            return join(idx, points)

        # as on a jitted function: the program a call runs
        pip_dense_join.lower = lambda points: join.lower(idx, points)
        return pip_dense_join
    # sorted-path defaults (wider: its f32 absolute-coordinate cell
    # assignment carries more error than the dense path's projection).
    # The margin additionally covers the cell-edge sagitta — the gap
    # between the true gnomonic cell boundary (which assigns points)
    # and the straight lon/lat chord the chips were clipped against
    # (round-4: a continent-extent res-2 join silently dropped points
    # inside that band)
    eps = 1e-5 if eps is None else eps
    if margin_eps is None:
        # margin from point_to_cell_jax_margin is PLANAR DEGREES, and
        # idx.sagitta_deg is the exact bound over this index's cells
        # (a radians-valued global sample here understated the band
        # 57x and missed high-latitude cells — round-4 review)
        margin_eps = max(3e-5, 2.0 * idx.sagitta_deg)

    def pip_sorted_join(points: jnp.ndarray):
        absolute = points + idx.origin.astype(points.dtype)
        cells, margin = grid.point_to_cell_jax_margin(absolute, idx.res)
        zone, uncertain = pip_assign(points, cells, idx, eps)
        uncertain |= margin < margin_eps
        inb = grid.point_in_bounds_jax(absolute)
        near_edge = jnp.zeros_like(inb)
        # 8-neighborhood offsets: diagonals matter for points just outside
        # a domain corner on both axes
        for dx in (-eps, 0., eps):
            for dy in (-eps, 0., eps):
                if dx == 0. and dy == 0.:
                    continue
                off = jnp.asarray([dx, dy], points.dtype)
                near_edge |= grid.point_in_bounds_jax(
                    absolute + off) != inb
        return jnp.where(inb, zone, jnp.int32(-1)), uncertain | near_edge

    return jax.jit(pip_sorted_join)


def _resolve_chunk(chunk: Optional[int]) -> int:
    """Caller-supplied chunk rows, else ``mosaic.stream.chunk.rows``
    (the previous hard-coded 262_144 is now that key's default)."""
    if chunk is not None:
        return int(chunk)
    from ..config import default_config
    return int(default_config().stream_chunk_rows)


def make_streamed_pip_join(idx, grid: IndexSystem,
                           polys: Optional[GeometryArray] = None,
                           chunk: Optional[int] = None,
                           eps: Optional[float] = None,
                           margin_eps: Optional[float] = None,
                           precision: str = "auto"):
    """End-to-end chunked join with transfer/compute/recheck overlap.

    The single-shot path stages the WHOLE point batch on device, runs
    one launch, then rechecks on host — three serial phases.  This
    wrapper cuts the batch into ``chunk``-row pieces and runs them
    through :func:`mosaic_tpu.perf.pipeline.stream`: the localize +
    upload of chunk N+1 rides along with device compute on chunk N,
    and the f64 host recheck of chunk N−1 runs on the pipeline's
    worker thread.  Exactness is untouched — same kernel, same
    recheck authority (``polys`` is required for a sorted
    :class:`PIPIndex`, optional for dense).

    Returns ``run(points64_abs) -> (zone [N] int32, rechecked
    count)``."""
    chunk = _resolve_chunk(chunk)
    from ..perf.jit_cache import kernel_cache
    # named jit-cache entry (not a bare jax.jit) so the kernel ledger
    # can attribute the streamed join's wall time to "pip/streamed"
    fn = kernel_cache.get_or_build(
        "pip/streamed", (id(idx), id(grid), eps, margin_eps, precision),
        lambda: make_pip_join_fn(idx, grid, eps, margin_eps, precision))
    recheck = host_recheck_fn(idx, polys)
    origin = np.asarray(idx.origin)
    ledger_key = (id(idx), id(grid), eps, margin_eps, precision)

    def run(points64: np.ndarray):
        import time as _time
        from ..obs import metrics, tracer
        from ..obs.context import root_trace
        from ..obs.inflight import checkpoint
        from ..obs.profiler import ledger
        checkpoint("pip_join/streamed")   # cancel before first chunk;
        # stream() itself re-probes at every chunk boundary
        points64 = np.asarray(points64, np.float64)[:, :2]
        n = len(points64)
        zone_out = np.empty(n, np.int32)
        state = {"rechecked": 0, "recheck_s": 0.0}

        def put(sl):
            # f64 origin shift BEFORE the f32 cast (= localize());
            # device_put is async, so this overlaps the running launch
            return jax.device_put(np.asarray(
                points64[sl] - origin[None], np.float32))

        def consume(i, sl, host):
            z, unc = host
            t0 = _time.perf_counter()
            zone_out[sl] = recheck(points64[sl], z, unc)
            state["recheck_s"] += _time.perf_counter() - t0
            state["rechecked"] += int(unc.sum())

        def observe(i, sl, seconds):
            ledger.observe("pip/streamed", ledger_key, seconds,
                           rows=sl.stop - sl.start)

        with root_trace("pip_join"), tracer.span("pip_join/streamed"):
            stream(chunk_rows(n, chunk), compute=fn, put=put,
                   consume=consume, observe=observe,
                   site="pip_join/streamed")
        if metrics.enabled:
            # host f64 recheck seconds, overlapped with the device by
            # the pipeline's worker thread
            metrics.count("pip_join/recheck_s", state["recheck_s"])
        return zone_out, state["rechecked"]

    return run


# ----------------------------------------------------------- sharded path

#: padding rows in the sharded streamed path get this local-frame
#: coordinate (degrees): far outside every workload extent, so both
#: index paths resolve them to zone -1 without tripping the f64
#: recheck, yet small enough that f32 trig in the projections stays
#: finite (1e9-style sentinels risk inf/nan there)
_PAD_SENTINEL_DEG = 4.0e3


def _shard_skew_readback(zones_padded: np.ndarray, D: int):
    """Per-shard matched-candidate counts from a [D*rows] zone vector
    (padding rows read zone -1 and drop out).  Records the skew gauge,
    its time series, and the max-candidates gauge."""
    from ..obs import metrics
    c = (zones_padded.reshape(D, -1) >= 0).sum(axis=1)
    mean = float(c.mean())
    skew = float(c.max()) / mean if mean else 1.0
    metrics.gauge("shard/skew/pip_join", skew)
    # same quantity as a distribution: shard/skew_series/pip_join_p50/
    # p95/p99 expose how imbalance evolves, not just the last readback
    metrics.observe("shard/skew_series/pip_join", skew)
    metrics.gauge("shard/candidates_max/pip_join", float(c.max()))
    return c


def make_sharded_streamed_pip_join(idx, grid: IndexSystem, mesh,
                                   polys: Optional[GeometryArray] = None,
                                   chunk: Optional[int] = None,
                                   eps: Optional[float] = None,
                                   margin_eps: Optional[float] = None,
                                   axis: str = "data",
                                   refresh: Optional[int] = None,
                                   nbins: int = 16):
    """The sharded flagship: :func:`make_streamed_pip_join` composed
    with the mesh.  One pipeline, three layers of the perf stack:

    * **double-buffered staging** — chunks flow through
      ``perf.pipeline.stream``: the scatter (host device_put of chunk
      N+1, split across the mesh by ``NamedSharding``) overlaps the
      sharded compute on chunk N, and the f64 recheck of chunk N−1
      drains on the pipeline's worker thread.
    * **bucketed kernel cache** — each chunk pads (sentinel rows, zone
      −1 by construction) to ``pow2_bucket(rows / D) * D`` and the
      jitted sharded kernel is keyed into
      ``perf.jit_cache.kernel_cache`` per (index, mesh, bucket): one
      XLA compile per bucket per mesh shape, zero in a warm process.
    * **skew-aware placement** — a :class:`.placement.SkewRebalancer`
      learns per-grid-cell matched-candidate density from every
      consumed chunk (free: the zones are already on host) and, every
      ``refresh`` chunks (``mosaic.shard.skew.refresh``, default 16),
      greedily re-packs cells onto shards; rows then scatter to
      per-shard slots via :func:`.placement.placement_slots` instead
      of arrival order.  The inverse permutation is applied on the
      host gather, so results are bit-for-bit identical to the
      single-device streamed path — placement only moves *where* each
      row is computed.

    ``polys`` is required for a sorted :class:`PIPIndex` (recheck
    authority), optional for dense.  Returns ``run(points64_abs) ->
    (zone [N] int32, rechecked count)``; ``run.rebalancer`` exposes
    the placement pass for inspection."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..config import default_config
    from ..obs import metrics
    from ..perf.bucketing import pow2_bucket
    from ..perf.jit_cache import kernel_cache
    from .placement import SkewRebalancer, placement_slots

    chunk = _resolve_chunk(chunk)
    fn = make_pip_join_fn(idx, grid, eps, margin_eps)
    recheck = host_recheck_fn(idx, polys)
    origin = np.asarray(idx.origin)
    D = mesh.shape[axis]
    pts_sharding = NamedSharding(mesh, P(axis, None))
    out_sharding = (NamedSharding(mesh, P(axis)),
                    NamedSharding(mesh, P(axis)))
    if refresh is None:
        refresh = default_config().shard_skew_refresh
    rebalancer = SkewRebalancer(D, refresh=refresh, nbins=nbins)
    idx_bytes = sum(int(np.asarray(leaf).nbytes)
                    for leaf in jax.tree_util.tree_leaves(idx))

    def kernel(rows):
        # one jit wrapper per padded bucket per mesh; the entry closes
        # over idx and the mesh-bound shardings, pinning both ids
        return kernel_cache.get_or_build(
            "pip/sharded_stream",
            (id(idx), id(mesh), axis, rows, eps, margin_eps),
            lambda: jax.jit(fn, in_shardings=(pts_sharding,),
                            out_shardings=out_sharding))

    def run(points64: np.ndarray):
        import time as _time
        from ..obs import tracer
        from ..obs.context import root_trace
        from ..obs.inflight import checkpoint
        checkpoint("pip_join/sharded_streamed")
        points64 = np.asarray(points64, np.float64)[:, :2]
        n = len(points64)
        zone_out = np.empty(n, np.int32)
        state = {"rechecked": 0, "slots": {}, "recheck_s": 0.0}

        def put(sl):
            rows = sl.stop - sl.start
            per = pow2_bucket(-(-rows // D), floor=64)
            pref = rebalancer.preferred(points64[sl])
            slots = placement_slots(pref, rows, D, per)
            buf = np.full((per * D, 2), _PAD_SENTINEL_DEG, np.float32)
            # f64 origin shift BEFORE the f32 cast (= localize()), same
            # values as the single-device put — only the row order and
            # padding differ
            buf[slots] = (points64[sl] - origin[None]).astype(np.float32)
            state["slots"][sl.start] = slots
            # device_put against the sharding splits the buffer across
            # the mesh asynchronously, overlapping the running launch
            dev = jax.device_put(buf, pts_sharding)
            if metrics.enabled:
                # where the rows landed, read off the staged array's own
                # shards (padding rows included)
                for sh in dev.addressable_shards:
                    d = sh.device
                    metrics.count("shard/staged_rows/pip_join/"
                                  f"{d.platform}:{d.id}",
                                  float(sh.data.shape[0]))
            return per * D, dev

        def compute(staged):
            rows, dev = staged
            return kernel(rows)(dev)

        def consume(i, sl, host):
            zp, up = host
            zp = np.asarray(zp)
            slots = state["slots"].pop(sl.start)
            z = zp[slots]
            unc = np.asarray(up)[slots]
            t0 = _time.perf_counter()
            zone_out[sl] = recheck(points64[sl], z, unc)
            state["recheck_s"] += _time.perf_counter() - t0
            state["rechecked"] += int(unc.sum())
            # feedback is free here — the shard results are already on
            # host
            rebalancer.observe(points64[sl], z >= 0)
            if metrics.enabled:
                c = _shard_skew_readback(zp, D)
                w = state.get("weights")
                state["weights"] = c if w is None else w + c
                metrics.gauge("shard/skew_planned/pip_join",
                              rebalancer.planned_skew())

        def observe(i, sl, seconds):
            from ..obs.profiler import ledger
            rows = sl.stop - sl.start
            padded = pow2_bucket(-(-rows // D), floor=64) * D
            # same key shape as the kernel() cache entry, so the ledger
            # row lines up with the per-bucket jit-cache kernel
            ledger.observe("pip/sharded_stream",
                           (id(idx), id(mesh), axis, padded, eps,
                            margin_eps), seconds, rows=rows)

        t0 = _time.perf_counter()
        with root_trace("pip_join"), \
                tracer.span("pip_join/sharded_streamed"):
            stream(chunk_rows(n, chunk), compute=compute, put=put,
                   consume=consume, observe=observe,
                   site="pip_join/sharded")
        if metrics.enabled:
            # per-device wall-time attribution: the run's matched-row
            # counts per shard (summed over chunks) are the load share
            from ..obs.devicemon import devicemon, mesh_device_keys
            devicemon.attribute("pip_join",
                                _time.perf_counter() - t0,
                                state.get("weights"),
                                mesh_device_keys(mesh))
        if metrics.enabled:
            metrics.gauge("collective/replicated_index_bytes",
                          float(idx_bytes) * D)
            metrics.gauge("shard/points_per_shard/pip_join", n / D)
            metrics.count("collective/points_scatter_bytes", 8.0 * n)
            # host f64 recheck seconds, as on the single-chip path
            metrics.count("pip_join/recheck_s", state["recheck_s"])
        return zone_out, state["rechecked"]

    run.rebalancer = rebalancer
    return run


def make_store_sharded_pip_join(store, idx, grid: IndexSystem, mesh,
                                polys: Optional[GeometryArray] = None,
                                chunk: Optional[int] = None,
                                eps: Optional[float] = None,
                                margin_eps: Optional[float] = None,
                                axis: str = "data",
                                refresh: Optional[int] = None,
                                nbins: int = 16):
    """The sharded flagship fed from an out-of-core chip store.

    Same three-layer pipeline as :func:`make_sharded_streamed_pip_join`
    — double-buffered staging, bucketed kernel cache, skew-aware
    placement — but the chunk source is
    :meth:`~..store.reader.ChipStore.iter_chunks`: a GENERATOR that
    prunes partitions against the query bbox from the manifest alone,
    then reads one shard at a time off disk.  The host never holds
    more than the pipeline's look-ahead window, so the dataset can be
    arbitrarily larger than RAM; a pruned partition contributes ZERO
    staged bytes (provable from ``run.staged_bytes_by_partition`` and
    the memwatch ledger's ``pip_join/store/staged`` site).

    Placement is PARTITION-level here: every row of a store partition
    inherits the shard the :class:`.placement.SkewRebalancer` prefers
    for that partition's bbox centroid, so the placement pass moves
    whole partitions between devices instead of individual rows — the
    granularity the store's on-disk layout already paid for (density
    feedback still learns from every consumed row, as before).
    Results stay bit-for-bit identical to the in-memory sharded path
    over the same points in store order: placement and padding only
    move *where* rows are computed, and the f64 host recheck is the
    same authority.

    Returns ``run(bbox=None) -> (zone [rows] int32, rechecked
    count)`` over the scanned rows in store order (manifest partition
    order, ingest order within a partition).  ``run.rebalancer``
    exposes the placement pass; after each call
    ``run.staged_bytes_by_partition`` maps cell id -> bytes that
    partition's rows staged (row-proportional share of each chunk's
    padded buffer)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..config import default_config
    from ..obs import metrics
    from ..perf.bucketing import pow2_bucket
    from ..perf.jit_cache import kernel_cache
    from .placement import SkewRebalancer, placement_slots

    chunk = _resolve_chunk(chunk)
    fn = make_pip_join_fn(idx, grid, eps, margin_eps)
    recheck = host_recheck_fn(idx, polys)
    origin = np.asarray(idx.origin)
    D = mesh.shape[axis]
    pts_sharding = NamedSharding(mesh, P(axis, None))
    out_sharding = (NamedSharding(mesh, P(axis)),
                    NamedSharding(mesh, P(axis)))
    if refresh is None:
        refresh = default_config().shard_skew_refresh
    rebalancer = SkewRebalancer(D, refresh=refresh, nbins=nbins)
    idx_bytes = sum(int(np.asarray(leaf).nbytes)
                    for leaf in jax.tree_util.tree_leaves(idx))
    # partition bbox centroids: the rebalancer's placement key — one
    # preferred-shard query per partition span, not per row
    cent = {p.cell: ((p.bbox[0] + p.bbox[2]) / 2.0,
                     (p.bbox[1] + p.bbox[3]) / 2.0)
            for p in store.partitions}
    if default_config().heat_prior:
        # seed placement from accumulated partition heat (obs/heat.py)
        # — a pure hint: the rebalancer only moves rows between
        # shards, so outputs stay bit-identical to an unprimed run
        from ..obs.heat import heat
        hp = heat.prior(nbins, store.bbox, cent)
        if hp is not None:
            rebalancer.prime(np.asarray(store.bbox, np.float64), hp)
            if metrics.enabled:
                metrics.count("heat/prior_primes")

    def kernel(rows):
        # shares the in-memory sharded path's cache family: a store
        # query and an array query of the same bucket reuse one compile
        return kernel_cache.get_or_build(
            "pip/sharded_stream",
            (id(idx), id(mesh), axis, rows, eps, margin_eps),
            lambda: jax.jit(fn, in_shardings=(pts_sharding,),
                            out_shardings=out_sharding))

    def run(bbox=None):
        from ..obs import tracer
        from ..obs.context import root_trace
        from ..obs.inflight import checkpoint
        checkpoint("pip_join/store")
        state = {"rechecked": 0, "slots": {}, "weights": None}
        staged_by_part: dict = {}
        rows_total = 0

        def put(ck):
            rows = ck.rows
            per = pow2_bucket(-(-rows // D), floor=64)
            pref = None
            if rebalancer.armed:
                # whole-partition placement: each span's rows go where
                # the rebalancer wants that partition's centroid
                cpts = np.asarray([cent[c] for c, _ in ck.parts],
                                  np.float64)
                pref = np.repeat(rebalancer.preferred(cpts),
                                 [r for _, r in ck.parts])
            slots = placement_slots(pref, rows, D, per)
            buf = np.full((per * D, 2), _PAD_SENTINEL_DEG, np.float32)
            buf[slots] = (ck.points - origin[None]).astype(np.float32)
            state["slots"][ck.offset] = slots
            # per-partition staging ledger: this chunk's padded buffer
            # split across its spans by row share (cumulative rounding
            # so the shares sum EXACTLY to buf.nbytes — the ledger
            # then reconciles against pipeline/h2d_bytes byte for
            # byte).  A pruned partition never appears here: it never
            # reached a chunk.
            seen = acc = 0
            for c, r in ck.parts:
                seen += r
                share = buf.nbytes * seen // rows - acc
                acc += share
                staged_by_part[c] = staged_by_part.get(c, 0) + share
            return per * D, jax.device_put(buf, pts_sharding)

        def compute(staged):
            rows, dev = staged
            return kernel(rows)(dev)

        def consume(i, ck, host):
            nonlocal rows_total
            zp, up = host
            zp = np.asarray(zp)
            slots = state["slots"].pop(ck.offset)
            z = zp[slots]
            unc = np.asarray(up)[slots]
            zone = recheck(ck.points, z, unc)
            state["rechecked"] += int(unc.sum())
            rows_total += ck.rows
            # density feedback stays row-level (free: already on host)
            rebalancer.observe(ck.points, z >= 0)
            if metrics.enabled:
                c = _shard_skew_readback(zp, D)
                w = state.get("weights")
                state["weights"] = c if w is None else w + c
                metrics.gauge("shard/skew_planned/pip_join",
                              rebalancer.planned_skew())
            return zone

        def observe(i, ck, seconds):
            from ..obs.profiler import ledger
            padded = pow2_bucket(-(-ck.rows // D), floor=64) * D
            ledger.observe("pip/sharded_stream",
                           (id(idx), id(mesh), axis, padded, eps,
                            margin_eps), seconds, rows=ck.rows)

        import time as _time
        t0 = _time.perf_counter()
        with root_trace("pip_join"), \
                tracer.span("pip_join/store_streamed"):
            zones = stream(store.iter_chunks(bbox=bbox,
                                             chunk_rows=chunk),
                           compute=compute, put=put, consume=consume,
                           observe=observe, site="pip_join/store")
        zone_out = np.concatenate(zones) if zones \
            else np.empty(0, np.int32)
        run.staged_bytes_by_partition = staged_by_part
        if staged_by_part:
            # per-partition staged bytes feed heat + the query's
            # durable history record (rows already fed at chunk emit)
            from ..obs.heat import heat
            from ..obs.inflight import note_partition_bytes
            for c, b in staged_by_part.items():
                heat.touch(c, nbytes=b, scans=0)
            note_partition_bytes(staged_by_part)
        if metrics.enabled:
            from ..obs.devicemon import devicemon, mesh_device_keys
            devicemon.attribute("pip_join",
                                _time.perf_counter() - t0,
                                state.get("weights"),
                                mesh_device_keys(mesh))
            metrics.gauge("collective/replicated_index_bytes",
                          float(idx_bytes) * D)
            metrics.gauge("shard/points_per_shard/pip_join",
                          rows_total / D)
            metrics.count("collective/points_scatter_bytes",
                          8.0 * rows_total)
            metrics.count("pip_join/store_points", float(rows_total))
            metrics.count("pip_join/store_chunks", float(len(zones)))
        return zone_out, state["rechecked"]

    run.rebalancer = rebalancer
    run.staged_bytes_by_partition = {}
    return run


# ------------------------------------------------- adaptive refinement

def _chips_clean(chips: ChipSet) -> bool:
    """True when a chipset's index is *clean*: no cell id appears in
    both the core and border sets, and no cell is core for two
    polygons — the same two conditions whose violation rejects the
    dense fast path (overlap_regime / duplicate_core).

    Why it matters: in a clean index a core-confident device hit
    implies NO other polygon intersects that cell at all (any
    intersection would have produced a chip there), so the core zone
    is the unique container; border-only hits take the first border
    slot, and the stable build sort keeps slots in geom-id order, so
    they resolve to the lowest containing id — exactly
    :func:`pip_host_truth`'s first-match rule.  Hence every point's
    full (device + f64 recheck) output equals the host oracle, at ANY
    resolution, which is what makes refined-vs-flat bit-parity a
    theorem instead of a hope.  An unclean chipset (overlapping
    polygons sharing a core cell) voids that argument — the refined
    join then declines to refine and runs the flat path unchanged."""
    core = chips.is_core
    core_cells = chips.cell_id[core]
    if len(np.intersect1d(core_cells, chips.cell_id[~core])):
        return False
    return len(np.unique(core_cells)) == len(core_cells)


def make_refined_pip_join(polys: GeometryArray, grid: IndexSystem,
                          res: int,
                          chunk: Optional[int] = None,
                          eps: Optional[float] = None,
                          margin_eps: Optional[float] = None,
                          precision: str = "auto"):
    """Adaptive per-cell refinement of the flagship join.

    The flat join pays ``max_dup`` serial chip probes per point — the
    worst cell's duplication sets every point's cost.  This wrapper
    starts at the caller's ``res`` exactly like the flat path, measures
    per-cell candidate-pair selectivity from the first batch's leading
    ``mosaic.join.refine.sample.rows`` points, and re-tessellates ONLY
    the dense border cells' polygons ``mosaic.join.refine.depth``
    levels deeper (arxiv 1802.09488's adaptive-grid argument).  Points
    the f64 device cell kernel routes to a dense cell run against the
    refined index (smaller chips, lower dup); everyone else runs
    against the *same* base index the flat path uses.

    Bit-parity: both levels are gated on :func:`_chips_clean` — a
    clean index's output equals :func:`pip_host_truth` for every
    point, so routing points between two clean levels cannot change a
    single zone.  Overlap regimes fail the gate and decline to refine
    (flat path, unchanged).  The refined part's recheck authority is
    the polygon SUBSET whose bboxes touch a dense cell (order
    preserved, ids remapped), which provably contains every polygon
    that can hold a dense-routed point.

    Strategy selection is the planner's ``refine/`` decision
    (:meth:`~..sql.planner.CostPlanner.decide_refine`): learned
    refined-vs-flat coefficients, cold dense-pair-fraction crossover,
    ``mosaic.planner.force.refine`` pin, and the
    ``mosaic.join.refine.enabled`` kill switch that beats any pin.
    Kernels live in ``perf.jit_cache`` under the ``pip/refined``
    family keyed per (level, pow2 row bucket) — a warm process with a
    persistent cache dir compiles nothing new.  Any failure inside the
    refined path (fault site ``join.refine``) transparently re-runs
    the batch on the flat path (``refine_bailout`` event +
    ``pip_join/refine_bailouts`` counter), mirroring FusionBailout.

    Returns ``run(points64_abs) -> (zone [N] int32, rechecked count)``
    with ``run.last_decision`` (the planner pick) and ``run.stats``
    (levels / cells_refined / cells_flat / refined_points /
    flat_points for the most recent call)."""
    import time as _time
    from ..config import default_config
    from ..core.tessellate import tessellate_subset
    from ..obs import metrics
    from ..obs.inflight import (QueryCancelled, checkpoint, note_refine,
                                note_strategies)
    from ..perf.bucketing import pow2_bucket
    from ..perf.jit_cache import kernel_cache
    from ..resilience import faults
    from ..sql.planner import Decision, planner

    chunk = _resolve_chunk(chunk)
    chips = tessellate(polys, res, grid, keep_core_geom=False)
    idx_base = build_pip_index(polys, res, grid, chips=chips,
                               dense="never")
    clean_base = _chips_clean(chips)
    recheck_base = host_recheck_fn(idx_base, polys)
    b_cells = chips.cell_id[~chips.is_core]
    u_cells, u_dup = (np.unique(b_cells, return_counts=True)
                      if len(b_cells) else
                      (np.empty(0, np.int64), np.empty(0, np.int64)))
    state = {"probed": False, "dense": np.empty(0, np.int64),
             "frac": 0.0, "depth": 0, "ref": None, "ref_unclean": False,
             "flat": None, "route_host": False}

    def _route_cells(pts64: np.ndarray) -> np.ndarray:
        """Base-level cell ids for the hot/cold routing split, via the
        jitted device kernel (f64 under the global x64 switch,
        canonical-pinned against the host path by
        tests/test_h3_canonical.py) — the interpreted host assignment
        at flagship sizes costs more than the join itself.  Routing is
        never answer authority: a cold-routed point runs the full base
        index, and _ensure_refined's bbox inflation holds every
        polygon that can contain a hot-routed point, so either routing
        outcome yields the oracle zone."""
        rows = len(pts64)
        if rows == 0:
            return np.empty(0, np.int64)
        if not state["route_host"]:
            try:
                per = pow2_bucket(rows, floor=64)
                buf = np.empty((per, 2), np.float64)
                buf[:rows] = pts64
                buf[rows:] = pts64[0]
                fn = kernel_cache.get_or_build(
                    "pip/route", (id(grid), res, per),
                    lambda: jax.jit(
                        lambda p: grid.point_to_cell_jax(p, res)))
                return np.asarray(fn(jnp.asarray(buf)))[:rows]
            except NotImplementedError:     # host-only grid
                state["route_host"] = True
                if metrics.enabled:
                    metrics.count("pip_join/route_host")
        return grid.point_to_cell(pts64, res)

    def _probe(points64: np.ndarray) -> None:
        """Sticky selectivity probe: per-border-cell estimated
        candidate pairs = (sample points in cell) x (chips in cell)."""
        cfg = default_config()
        sample = points64[:max(1, int(cfg.join_refine_sample_rows))]
        if not len(u_cells) or not len(sample):
            return
        cells = _route_cells(np.asarray(sample, np.float64))
        pos = np.searchsorted(u_cells, cells)
        posc = np.clip(pos, 0, len(u_cells) - 1)
        valid = (pos < len(u_cells)) & (u_cells[posc] == cells)
        counts = np.bincount(posc[valid], minlength=len(u_cells))
        pairs = counts.astype(np.float64) * u_dup
        total = float(pairs.sum())
        floor = int(cfg.join_refine_dup_threshold)
        sel = np.nonzero((u_dup >= floor) & (counts > 0))[0]
        cap = max(1, int(cfg.join_refine_max_cells))
        if len(sel) > cap:
            sel = sel[np.argsort(-pairs[sel], kind="stable")[:cap]]
        state["dense"] = np.sort(u_cells[sel])
        state["frac"] = float(pairs[sel].sum()) / total if total else 0.0

    def _ensure_refined(depth: int) -> bool:
        """Build the deeper index over the dense cells' polygons once
        (sticky at the first requested depth); False = parity gate
        failed at the refined level, caller must run flat."""
        if state["ref"] is not None:
            return True
        if state["ref_unclean"]:
            return False
        dense = state["dense"]
        if not len(dense):
            state["ref"] = {"empty": True}
            state["depth"] = max(1, int(depth))
            return True
        verts, counts = grid.cell_boundary(dense)
        m = np.arange(verts.shape[1])[None, :] < counts[:, None]
        vx, vy = verts[..., 0], verts[..., 1]
        cb = np.stack([np.where(m, vx, np.inf).min(1),
                       np.where(m, vy, np.inf).min(1),
                       np.where(m, vx, -np.inf).max(1),
                       np.where(m, vy, -np.inf).max(1)], axis=1)
        # inflate by the chord-vs-gnomonic sagitta: the true cell edge
        # can bow past the vertex-chord bbox, and the subset must hold
        # EVERY polygon that can contain a dense-routed point
        pad = max(1e-9, 2.0 * float(idx_base.sagitta_deg))
        cb += np.array([-pad, -pad, pad, pad])
        pb = polys.bboxes()
        inter = ~((pb[:, None, 0] > cb[None, :, 2]) |
                  (pb[:, None, 2] < cb[None, :, 0]) |
                  (pb[:, None, 1] > cb[None, :, 3]) |
                  (pb[:, None, 3] < cb[None, :, 1]))
        sub_ids = np.nonzero(inter.any(axis=1))[0]
        depth = max(1, int(depth))
        sub, sub_chips = tessellate_subset(polys, sub_ids, res + depth,
                                           grid, keep_core_geom=False)
        if not _chips_clean(sub_chips):
            state["ref_unclean"] = True
            return False
        idx_ref = build_pip_index(sub, res + depth, grid,
                                  chips=sub_chips, dense="never")
        state["ref"] = {"idx": idx_ref, "orig": sub_ids.astype(np.int32),
                        "recheck": host_recheck_fn(idx_ref, sub)}
        state["depth"] = depth
        return True

    def _kernel(idx_level, rows: int):
        # one entry per (level index, pow2 bucket): a warm process
        # with a persistent cache dir loads both executables from disk
        return kernel_cache.get_or_build(
            "pip/refined",
            (id(idx_level), idx_level.res, rows, eps, margin_eps,
             precision),
            lambda: jax.jit(make_pip_join_fn(
                idx_level, grid, eps, margin_eps, precision)))

    def _run_part(idx_level, recheck, pts64: np.ndarray):
        rows = len(pts64)
        if rows == 0:
            return np.empty(0, np.int32), 0
        # greedy pow2 decomposition rather than one rounded-up bucket:
        # the hot/cold split lands wherever the data says (a 51% part
        # would pad to ~2x its rows, and padding rows run the kernel
        # at full price).  Stopping an eighth below the leading bucket
        # bounds the waste at 12.5% across at most 5 launches, every
        # one still cached per (level, bucket).
        lead = 1 << (rows.bit_length() - 1)
        floor = max(64, lead >> 3)
        origin = np.asarray(idx_level.origin)[None]
        z = np.empty(rows, np.int32)
        unc = np.empty(rows, bool)
        s = 0
        while s < rows:
            rem = rows - s
            per = max(floor, 1 << (rem.bit_length() - 1))
            take = min(rem, per)
            buf = np.full((per, 2), _PAD_SENTINEL_DEG, np.float32)
            # f64 origin shift before the f32 cast (= localize()); pad
            # rows keep the sentinel and resolve to -1 without recheck
            buf[:take] = np.asarray(pts64[s:s + take] - origin,
                                    np.float32)
            zz, uu = _kernel(idx_level, per)(jnp.asarray(buf))
            z[s:s + take] = np.asarray(zz)[:take]
            unc[s:s + take] = np.asarray(uu)[:take]
            s += take
        return recheck(pts64, z, unc), int(unc.sum())

    def _flat():
        if state["flat"] is None:
            state["flat"] = make_streamed_pip_join(
                idx_base, grid, polys=polys, chunk=chunk, eps=eps,
                margin_eps=margin_eps, precision=precision)
        return state["flat"]

    def _refined(points64: np.ndarray):
        from ..obs import tracer
        from ..obs.context import root_trace
        ref = state["ref"]
        dense = state["dense"]
        n = len(points64)
        zone = np.empty(n, np.int32)
        rechecked = refined_pts = 0
        with root_trace("pip_join"), tracer.span("pip_join/refined"):
            for sl in chunk_rows(n, chunk):
                checkpoint()
                faults.maybe_fail("join.refine")
                pts = points64[sl]
                if len(dense) and "idx" in ref:
                    cells = _route_cells(pts)
                    pos = np.searchsorted(dense, cells)
                    posc = np.clip(pos, 0, len(dense) - 1)
                    hot = (pos < len(dense)) & (dense[posc] == cells)
                else:
                    hot = np.zeros(len(pts), bool)
                out = np.empty(len(pts), np.int32)
                za, ra = _run_part(idx_base, recheck_base, pts[~hot])
                out[~hot] = za
                if hot.any():
                    zb, rb = _run_part(ref["idx"], ref["recheck"],
                                       pts[hot])
                    orig = ref["orig"]
                    out[hot] = np.where(
                        zb >= 0, orig[np.clip(zb, 0, len(orig) - 1)],
                        np.int32(-1))
                    rechecked += rb
                    refined_pts += int(hot.sum())
                rechecked += ra
                zone[sl] = out
        return zone, rechecked, refined_pts

    def run(points64: np.ndarray):
        points64 = np.asarray(points64, np.float64)[:, :2]
        n = len(points64)
        if not state["probed"]:
            _probe(points64)
            state["probed"] = True
        if not clean_base:
            # parity gate, not a cost call: the clean-index theorem
            # doesn't hold here, so refinement is off the table no
            # matter what the planner (or a pin) would prefer
            d = Decision("refine", "flat",
                         "overlap regime at base level (parity gate)",
                         n, cost_key="refine/flat", key_n=n,
                         forced=True)
            d.depth = 0
            planner.record_decision(d)
        else:
            d = planner.decide_refine(n, state["frac"],
                                      idx_base.max_dup)
            if d.strategy == "refined" and \
                    not _ensure_refined(getattr(d, "depth", 1)):
                d.strategy = "flat"
                d.reason = ("overlap regime at refined level "
                            "(parity gate)")
                d.cost_key = "refine/flat"
                d.forced = True
                planner.record_decision(d)
        t0 = _time.perf_counter()
        refined_pts = 0
        bailed = False
        if d.strategy == "refined":
            try:
                zone, rechecked, refined_pts = _refined(points64)
            except (QueryCancelled, KeyboardInterrupt):
                raise
            except Exception as e:          # transparent flat fallback
                bailed = True
                if metrics.enabled:
                    metrics.count("pip_join/refine_bailouts")
                from ..obs.recorder import recorder
                recorder.record("refine_bailout",
                                error=type(e).__name__,
                                detail=str(e)[:200], rows=n)
                refined_pts = 0
                zone, rechecked = _flat()(points64)
        else:
            zone, rechecked = _flat()(points64)
        wall = _time.perf_counter() - t0
        planner.observe_decision(d, wall,
                                 rows_out=int((zone >= 0).sum()))
        depth = state["depth"] or int(getattr(d, "depth", 1) or 1)
        # stats describe what RAN (the decision object keeps what was
        # decided — they differ exactly when a bailout demoted the run)
        refined_run = (d.strategy == "refined" and not bailed
                       and state["ref"] is not None and refined_pts > 0)
        cells_refined = len(state["dense"]) if refined_run else 0
        stats = {
            "levels": [res, res + depth] if refined_run else [res],
            "cells_refined": cells_refined,
            "cells_flat": len(u_cells) - cells_refined,
            "refined_points": int(refined_pts),
            "flat_points": int(n - refined_pts),
            "strategy": "refined" if refined_run else "flat",
        }
        if metrics.enabled and refined_pts:
            metrics.count("pip_join/refined_points",
                          float(refined_pts))
        note_strategies({"refine": d.label + (" (bailout)" if bailed
                                              else "")})
        if refined_run:
            summary = (f"L{res}+{depth}: {cells_refined} refined / "
                       f"{stats['cells_flat']} flat cells, "
                       f"{refined_pts}/{n} pts")
        else:
            summary = "flat"
        note_refine({k: stats[k] for k in
                     ("cells_refined", "cells_flat", "refined_points",
                      "flat_points")}, summary=summary)
        run.stats = stats
        run.last_decision = d
        return zone, rechecked

    run.stats = None
    run.last_decision = None
    return run


def zone_histogram(zone: jnp.ndarray, num_zones: int) -> jnp.ndarray:
    """Per-zone match counts — the canonical aggregation after the join
    (reference: groupBy(index_id).count()).  A scatter-add segment sum
    (O(N), not an O(N·Z) one-hot); unmatched (-1) rows are dropped.
    Under pjit this lowers to a sharded segment-sum + psum over the data
    axis.

    ``.at[].add(mode="drop")`` normalizes negative indices NumPy-style
    *before* dropping, so -1 would wrap to the last zone; remap invalid
    rows to ``num_zones`` (genuinely out of bounds) so drop applies."""
    zone = jnp.where(zone < 0, jnp.int32(num_zones), zone)
    return jnp.zeros(num_zones, jnp.int32).at[zone].add(
        1, mode="drop", indices_are_sorted=False)


# --------------------------------------------------- dense lattice index
#
# The sorted-table path above is grid-agnostic but pays ~29 serial
# binary-search gathers per point; measured on TPU v5e that was 56% of
# the whole join (scratch: 1.9 s of a 3.4 s step at 4M points — TPU
# gathers cost ~16-30 ns per row regardless of row width).  For H3
# workloads that fit one icosahedron face (any city/metro/state-scale
# join), the H3 kernel's intermediate (face, a, b) lattice coords index
# a dense window table directly.  Everything a border cell needs (its
# chips' edges, their zone slots, the cell's zone ids and its wide
# flag) is packed into ONE lane-dense record row, and the record table
# is keyed by lattice cell, with the cell's entry code in a lane of its
# own: ONE row gather a point does the lookup and feeds the edge test
# in place.  (A separate int32 entry gather ahead of it cost twice the
# row gather on v5e: the table sat in VMEM, but an element gather runs
# at ~10 cycles a point.)  Design rule: one gather per point per
# logical step.

CORE_FLAG = np.int32(1) << 30
#: largest cell-keyed record table (bytes) a dense build lays out;
#: a window whose table would pass it keeps one row per border group
#: behind an int32 entry table, and pays a second gather a point
CELL_ROWS_MAX_BYTES = 256 << 20


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DensePIPIndex:
    """Device-resident dense-window tessellation index (H3, one face).

    rec    [W*H, R] f32  ``layout == "cell_rows"``: one record row per
                       window cell, at ``(a - a0) * H + (b - b0)``, read
                       by one row gather per point.  R is 5*E + Z + 2
                       rounded up to a multiple of 128 lanes; the lanes
                       hold blocks ``ax[0:E] | ay[0:E] | bx[0:E] |
                       by[0:E] | zslot[0:E] | gz[0:Z] | wide | code``:
                       the merged chip edges of the cell's border group
                       in the local frame (pad coords at +1e9 so they
                       never straddle/flag), each edge's zone slot (-1
                       pad), the group's distinct zone ids (-1 pad), its
                       wide flag (0/1) and the cell's entry code (-1
                       empty; CORE_FLAG|zone core; else the group
                       index).  A non-border cell's row is all pads but
                       its code.  The int lanes hold int32 bit patterns
                       (``lax.bitcast_convert_type``), so every zone id
                       stays exact
           [G, R] f32  ``layout == "group_rows"``: the same rows, one
                       per border group (code = the group index), when
                       the cell-keyed table would pass
                       CELL_ROWS_MAX_BYTES (windows reach 64M cells)
    entry  [W*H] i32   group rows only: the entry code per lattice
                       cell, gathered ahead of the group's row (None in
                       the cell-keyed layout, where the code lane holds
                       it; the host copy is always ``aux["entry"]``)
    origin [2] f64     local-frame origin (lon, lat)
    static: face0, a0, b0, W, H, res, err_lattice (margin threshold),
            n_zones, E (edge slots a group), Z (zone slots a group),
            groups (border groups), layout
    host-side aux (not traced): recheck CSR in f64 (see host_recheck_fn)

    A group is wide when its chip edges exceed E (a complex coastline
    cell): every point landing there is flagged uncertain and resolved
    by the exact f64 host recheck, so ONE wide cell cannot pad the whole
    table (real NYC zones: max 308 edges vs mean 19 made the kernel 12x
    slower than the synthetic bench).
    """

    entry: Optional[jnp.ndarray]
    rec: jnp.ndarray
    origin: np.ndarray
    face0: int
    a0: int
    b0: int
    W: int
    H: int
    res: int
    err_lattice: float
    n_zones: int
    E: int
    Z: int
    #: max |local degree| over window cells (+ slack); join queries
    #: beyond this are out-of-domain by construction
    ext_deg: float = 2.0
    groups: int = 0
    layout: str = "cell_rows"
    aux: Optional[dict] = None

    def tree_flatten(self):
        return ((self.entry, self.rec),
                (self.origin.tobytes(), self.face0, self.a0, self.b0,
                 self.W, self.H, self.res, self.err_lattice,
                 self.n_zones, self.E, self.Z, self.ext_deg,
                 self.groups, self.layout))

    @classmethod
    def tree_unflatten(cls, aux, children):
        origin = np.frombuffer(aux[0], np.float64)
        return cls(*children, origin, *aux[1:])

    @property
    def num_chips(self) -> int:
        return self.groups


def _host_lattice(grid, pts_deg: np.ndarray, res: int,
                  with_margin: bool = False):
    """f64 (face, a, b) of absolute lon/lat degree points (host truth),
    plus each point's lattice margin to its cell boundary when asked."""
    from ..core.index.h3 import hexmath as hm
    latlng = np.radians(np.asarray(pts_deg, np.float64)[:, ::-1])
    face, hex2d = hm.project_lattice(latlng, res)
    ijk = hm.hex2d_to_ijk(hex2d)
    out = (face, ijk[:, 0] - ijk[:, 2], ijk[:, 1] - ijk[:, 2])
    return out + (hm.hex2d_margin(hex2d),) if with_margin else out


#: why the last build_dense_pip_index call fell back (None = it
#: didn't) — surfaced so a workload quietly losing the fast path is
#: diagnosable (VERDICT round-3 weak #9); also counted in the tracer
#: as dense_reject/<reason>
LAST_DENSE_REJECT: Optional[str] = None


def _dense_reject(reason: str) -> None:
    global LAST_DENSE_REJECT
    LAST_DENSE_REJECT = reason
    try:
        from ..obs import tracer
        tracer.count(f"dense_reject/{reason}")
    except Exception:
        pass


def build_dense_pip_index(polys: GeometryArray, res: int, grid,
                          chips: Optional[ChipSet] = None,
                          precision: str = "auto"
                          ) -> Optional[DensePIPIndex]:
    """Build the dense-window index, or None when the workload doesn't
    fit the fast path (non-H3 grid, cells spanning icosahedron faces,
    window larger than the df Taylor bound, or overlapping polygons
    putting one cell in both core and border sets — the sorted-table
    path handles those).  The reject reason lands in
    ``LAST_DENSE_REJECT`` and the tracer counters."""
    global LAST_DENSE_REJECT
    LAST_DENSE_REJECT = None
    from ..core.geometry.padded import build_edges_np
    from ..core.index.h3.jaxkernel import (MAX_LOCAL_DEG, err_lattice_bound,
                                           pick_precision)
    from ..core.index.h3.system import H3IndexSystem

    if not isinstance(grid, H3IndexSystem):
        _dense_reject("non_h3_grid")
        return None
    if chips is None:
        chips = tessellate(polys, res, grid, keep_core_geom=False)
    if len(chips) == 0:
        _dense_reject("no_chips")
        return None

    cells = np.unique(chips.cell_id)
    centers = grid.cell_center(cells)                    # [C, 2] deg
    origin = _workload_origin(polys)
    _, circ = grid._cell_metrics_deg(res)                # max circumradius
    # 2x: circumradius is angular degrees; lon extent is circ/cos(lat)
    ext = float(max(np.max(np.abs(centers[:, 0] - origin[0])),
                    np.max(np.abs(centers[:, 1] - origin[1])))) + 2 * circ
    if ext > MAX_LOCAL_DEG - 0.1:
        _dense_reject("window_extent")
        return None
    face_c, a_c, b_c = _host_lattice(grid, centers, res)
    if len(np.unique(face_c)) != 1:
        _dense_reject("multi_face")
        return None
    # face-edge safety: every window cell must be interior enough that
    # no point of it can argmax to another face (facegap ≈ angular
    # distance to the face boundary; 0.02 ≈ 1.1 degrees of arc)
    from ..core.index.h3.hexmath import geo_to_xyz, face_center_xyz
    xyz = geo_to_xyz(np.radians(centers[:, ::-1]))
    dots = xyz @ face_center_xyz().T
    srt = np.sort(dots, axis=1)
    if np.min(srt[:, -1] - srt[:, -2]) < 0.02:
        _dense_reject("face_edge_band")
        return None

    core = chips.is_core
    core_cells = chips.cell_id[core]
    if len(np.intersect1d(core_cells, chips.cell_id[~core])):
        _dense_reject("overlap_regime")
        return None                                      # overlap regime
    if len(np.unique(core_cells)) != len(core_cells):
        _dense_reject("duplicate_core")
        return None

    face0 = int(face_c[0])
    a0, b0 = int(a_c.min()) - 1, int(b_c.min()) - 1
    W = int(a_c.max()) - a0 + 2
    H = int(b_c.max()) - b0 + 2
    if W * H > 64_000_000:
        _dense_reject("window_too_large")
        return None

    lat_of = {int(c): (int(a), int(b))
              for c, a, b in zip(cells, a_c, b_c)}

    entry = np.full(W * H, -1, np.int32)

    def lin(cell):
        a, b = lat_of[int(cell)]
        return (a - a0) * H + (b - b0)

    for c, z in zip(core_cells, chips.geom_id[core]):
        entry[lin(c)] = np.int32(z) | CORE_FLAG

    # ---- border groups: all chips of a cell merged into one edge soup
    b_cells = chips.cell_id[~core]
    b_zone = chips.geom_id[~core].astype(np.int32)
    border_idx = np.nonzero(~core)[0]
    order = np.argsort(b_cells, kind="stable")
    b_cells, b_zone = b_cells[order], b_zone[order]
    chip_geoms = chips.geoms.take(border_idx[order])
    A, B, M = build_edges_np(chip_geoms)                 # [Bc, cap, 2] f64
    cnt = M.sum(axis=1)

    ucells, ustart = np.unique(b_cells, return_index=True)
    G = len(ucells)
    gidx = np.searchsorted(ucells, b_cells)              # chip -> group
    gedges = np.bincount(gidx, weights=cnt).astype(np.int64)
    # record width covers the 98th-percentile group; wider groups are
    # truncated and their cells flagged always-uncertain (host f64
    # resolves them exactly) — one pathological cell must not pad the
    # kernel for every point
    emax = int(gedges.max()) if G else 0
    etarget = int(max(np.quantile(gedges, 0.98), 8)) if G else 8
    E = 8
    while E < min(emax, etarget):
        E *= 2
    E = min(E, 512)
    gwide_np = gedges > E
    if G and float(gwide_np.mean()) > 0.2:
        # most cells would bounce to host: dense is the wrong shape
        _dense_reject("pathological_cell")
        return None

    # distinct zones per group, first-appearance order; per-chip zslot
    Z = 1
    gzone_lists: list = [[] for _ in range(G)]
    zslot_chip = np.zeros(len(b_cells), np.int32)
    for i in range(len(b_cells)):
        zl = gzone_lists[gidx[i]]
        z = int(b_zone[i])
        if z not in zl:
            zl.append(z)
        zslot_chip[i] = zl.index(z)
    Z = max(1, max(len(zl) for zl in gzone_lists))
    gzones = np.full((G, Z), -1, np.int32)
    for g, zl in enumerate(gzone_lists):
        gzones[g, :len(zl)] = zl

    for g, c in enumerate(ucells):
        entry[lin(c)] = np.int32(g)

    # flatten valid edges in (group, chip, edge) order — already sorted
    flat_a = A[M]                                        # [Etot, 2] f64
    flat_b = B[M]
    edge_chip = np.repeat(np.arange(len(b_cells)), cnt.astype(np.int64))
    edge_group = gidx[edge_chip]
    edge_zslot = zslot_chip[edge_chip]
    gstart = np.zeros(G + 1, np.int64)
    np.cumsum(gedges, out=gstart[1:])
    pos = np.arange(len(flat_a)) - gstart[edge_group]

    R = -(-(5 * E + Z + 2) // 128) * 128
    # one row a group, then one pad row (what a non-border cell holds)
    rec = np.full((G + 1, R), 1e9, np.float32)
    irec = rec.view(np.int32)            # the int lanes, as bit patterns
    irec[:, 4 * E:5 * E + Z] = -1
    irec[:, 5 * E + Z:] = 0
    loc_a = flat_a - origin[None]
    loc_b = flat_b - origin[None]
    fits = pos < E                       # wide-group overflow truncated
    eg, ep = edge_group[fits], pos[fits]
    rec[eg, ep] = loc_a[fits, 0].astype(np.float32)
    rec[eg, E + ep] = loc_a[fits, 1].astype(np.float32)
    rec[eg, 2 * E + ep] = loc_b[fits, 0].astype(np.float32)
    rec[eg, 3 * E + ep] = loc_b[fits, 1].astype(np.float32)
    irec[eg, 4 * E + ep] = edge_zslot[fits]
    irec[:G, 5 * E:5 * E + Z] = gzones
    irec[:G, 5 * E + Z] = gwide_np
    irec[:G, 5 * E + Z + 1] = np.arange(G, dtype=np.int32)
    if W * H * R * 4 <= CELL_ROWS_MAX_BYTES:
        # keyed by lattice cell: a border cell's row is its group's, any
        # other cell's the pad row, and the code lane holds the entry
        layout = "cell_rows"
        border = (entry >= 0) & ((entry & CORE_FLAG) == 0)
        rec = rec[np.where(border, entry, G)]
        rec.view(np.int32)[:, 5 * E + Z + 1] = entry
    else:
        layout = "group_rows"
        rec = rec[:max(G, 1)]
    try:
        from ..obs import tracer
        tracer.count(f"dense_layout/{layout}")
    except Exception:
        pass

    prec = pick_precision(precision)
    ext_deg = float(ext) + 0.1
    err = err_lattice_bound(res, prec, ext_deg, localized=True)
    # widen by the cell-edge sagitta: points between the true (gnomonic)
    # cell boundary and the straight lon/lat chord the chips were
    # clipped against must re-rank on host (negligible at city
    # resolutions, dominant at coarse ones).  Exact over the window's
    # own cells; degrees -> lattice units via the gnomonic scale.
    from ..core.index.h3.constants import M_SQRT7, RES0_U_GNOMONIC
    sag_deg = grid.cells_edge_sagitta_deg(cells) if hasattr(
        grid, "cells_edge_sagitta_deg") else 0.0
    sag_lattice = 2.0 * np.radians(sag_deg) * M_SQRT7 ** res / \
        RES0_U_GNOMONIC
    err = max(err, sag_lattice)
    aux = {
        "flat_a": flat_a, "flat_b": flat_b,
        "edge_zslot": edge_zslot.astype(np.int64),
        "gstart": gstart, "gzones64": gzones.astype(np.int64),
        "entry": entry,
        "grid": grid, "polys": polys, "sag_lattice": sag_lattice,
    }
    return DensePIPIndex(
        entry=jnp.asarray(entry) if layout == "group_rows" else None,
        rec=jnp.asarray(rec), origin=origin, face0=face0,
        a0=a0, b0=b0, W=W, H=H, res=res, err_lattice=float(err),
        n_zones=len(polys), E=E, Z=Z, ext_deg=ext_deg, groups=G,
        layout=layout, aux=aux)


def make_dense_pip_join_fn(idx: DensePIPIndex, eps: float = EPS_EDGE_DEG,
                           precision: str = "auto",
                           margin_eps_deg: Optional[float] = None):
    """Jittable ``(idx, local_points) -> (zone, uncertain)`` on the
    dense index ``idx`` (passed again, so that its tables can be
    arguments of the executable; :func:`make_pip_join_fn` binds it).

    Exactness contract (same as the sorted path): every f32 hazard
    raises ``uncertain`` — (a) hex-boundary margin below the validated
    projection error bound (cell assignment could differ from f64),
    (b) nearest-face ambiguity, (c) edge-crossing tests within ``eps``
    of flipping (horizontal crossing distance or ray-through-vertex).
    Points beyond the window's local extent are out-of-domain by
    construction: zone -1, certain (their projection may even be outside
    the df Taylor validity radius, so it must not be consulted).
    host_recheck_fn resolves flagged points in f64."""
    from ..core.index.h3.jaxkernel import (FACEGAP_EPS, err_lattice_bound,
                                           pick_precision,
                                           project_lattice_jax)
    E, Z = idx.E, idx.Z
    cell_rows = idx.layout == "cell_rows"
    # margin threshold must match the arithmetic that actually runs —
    # idx.err_lattice was derived at build time, possibly on another
    # backend/precision; recompute for the resolved path and take the
    # wider of the two
    err_lat = max(idx.err_lattice, err_lattice_bound(
        idx.res, pick_precision(precision), idx.ext_deg, localized=True))
    if margin_eps_deg is not None:
        # honor a caller-requested degree band: degrees -> lattice units
        from ..core.index.h3.constants import M_SQRT7, RES0_U_GNOMONIC
        scale = M_SQRT7 ** idx.res / RES0_U_GNOMONIC
        err_lat = max(err_lat, margin_eps_deg * np.pi / 180.0 * scale)
    far_lim = np.float32(idx.ext_deg + 0.05)

    import os
    use_pallas = os.environ.get("MOSAIC_PIP_PALLAS", "").lower() in (
        "1", "true", "yes")
    if use_pallas:
        # the Pallas kernel runs df arithmetic regardless of the
        # requested precision; the margin threshold must match it
        err_lat = max(err_lat, err_lattice_bound(
            idx.res, "df", idx.ext_deg, localized=True))

    def pip_dense_join(idx, points):
        # named scopes group the kernel's ops by stage in a profile
        with jax.named_scope("project"):
            if use_pallas:
                # opt-in Pallas projection kernel (ops/pallas_projection.py)
                # until validated on hardware; same contract, same outputs
                from ..ops.pallas_projection import project_lattice_pallas
                face, ai, bi, margin, facegap = project_lattice_pallas(
                    points, idx.res,
                    # graftlint: ignore[jit-host-sync] — idx.origin is a host-side numpy constant closed over, folds at trace time
                    (float(idx.origin[0]), float(idx.origin[1])))
            else:
                face, ai, bi, margin, facegap = project_lattice_jax(
                    points, idx.res, idx.origin, precision=precision)
        with jax.named_scope("cell_lookup"):
            ia = ai - idx.a0
            ib = bi - idx.b0
            inw = ((face == idx.face0) & (ia >= 0) & (ia < idx.W) &
                   (ib >= 0) & (ib < idx.H))
            lidx = jnp.where(inw, ia * idx.H + ib, 0)
            # one row gather a point, then the whole row turned once so
            # points run along lanes, the layout the compiler picks for
            # the reductions over E; each block is then a row slice read
            # in place (lane slices of [N, R] get one relayout each)
            if cell_rows:
                rec = jnp.moveaxis(idx.rec[lidx], -1, 0)    # [R, N]
                code = jax.lax.bitcast_convert_type(rec[5 * E + Z + 1],
                                                    jnp.int32)
                e = jnp.where(inw, code, jnp.int32(-1))
            else:
                e = jnp.where(inw, idx.entry[lidx], jnp.int32(-1))
            is_core = (e >= 0) & ((e & CORE_FLAG) != 0)
            zone_core = jnp.where(is_core, e & ~CORE_FLAG, jnp.int32(-1))
            is_border = (e >= 0) & ~is_core

        with jax.named_scope("edge_pool"):
            if not cell_rows:
                g = jnp.where(is_border, e, 0)
                rec = jnp.moveaxis(idx.rec[g], -1, 0)   # [R, N]
            ax, ay = rec[:E], rec[E:2 * E]
            bx, by = rec[2 * E:3 * E], rec[3 * E:4 * E]
            zs = jax.lax.bitcast_convert_type(rec[4 * E:5 * E], jnp.int32)
            px = points[..., 0][None]
            py = points[..., 1][None]
            straddle = (ay <= py) != (by <= py)
            t = (py - ay) / jnp.where(by == ay, jnp.ones_like(by), by - ay)
            xi = ax + t * (bx - ax)
            crossed = straddle & (px < xi)
            near_cross = straddle & (jnp.abs(px - xi) < eps)
            near_vertex = (jnp.abs(py - ay) < eps) & \
                (px < jnp.maximum(ax, bx) + eps)
            edge_flag = jnp.any(near_cross | near_vertex, axis=0) & \
                is_border

        with jax.named_scope("zone_parity"):
            tail = jax.lax.bitcast_convert_type(rec[5 * E:], jnp.int32)
            # the zone of the first slot with odd parity: an elementwise
            # select over the Z slots, last slot first
            zone_border = jnp.full(is_border.shape, -1, jnp.int32)
            for z in reversed(range(Z)):
                cnt = jnp.sum(crossed & (zs == z), axis=0)
                zone_border = jnp.where((cnt & 1).astype(bool), tail[z],
                                        zone_border)
            zone = jnp.where(is_core, zone_core,
                             jnp.where(is_border, zone_border,
                                       jnp.int32(-1)))

        with jax.named_scope("flags"):
            far = (jnp.abs(points[..., 0]) > far_lim) | \
                (jnp.abs(points[..., 1]) > far_lim)
            wide = (tail[Z] != 0) & is_border
            uncertain = (margin < np.float32(err_lat)) | \
                (facegap < np.float32(FACEGAP_EPS)) | edge_flag | wide
            zone = jnp.where(far, jnp.int32(-1), zone)
            uncertain = uncertain & ~far
        return zone, uncertain

    return pip_dense_join


def host_recheck_fn(idx, polys: Optional[GeometryArray] = None):
    """Vectorized f64 host recheck bound to an index (either kind).

    Returns ``recheck(points64_abs, zone, uncertain) -> zone`` that
    reruns the flagged points through the SAME chip semantics in f64 —
    exact cell assignment (host lattice), exact crossing parity against
    the original unquantized chip edges.  Replaces the per-polygon
    Python loop (round-2 host_recheck), which did not scale: this is a
    handful of numpy passes over the flagged subset.  Flagged points
    inside the cell-edge sagitta band go to the original polygons
    instead (see ``recheck`` below).

    For a sorted ``PIPIndex`` (no dense aux tables) the recheck
    authority is the original polygons — pass ``polys``; the returned
    closure wraps :func:`host_recheck`.  (Round-4 fix: this used to
    raise AttributeError on the sorted index type.)"""
    if not isinstance(idx, DensePIPIndex):
        if polys is None:
            raise ValueError(
                "host_recheck_fn on a sorted PIPIndex needs the original "
                "polygons: host_recheck_fn(idx, polys)")
        return lambda pts, zone, uncertain: host_recheck(
            np.asarray(pts), np.asarray(zone), np.asarray(uncertain),
            polys)
    aux = idx.aux
    assert aux is not None, "recheck needs the build-time aux tables"
    entry = aux["entry"]
    Z = idx.Z
    # native-kernel tables, prepared ONCE at bind time (per-call work
    # must scale with the flagged subset, not the record table) —
    # and only when the native path can actually run
    try:
        from .. import native as _native
    except ImportError:
        _native = None
    if _native is not None and (_native.get_lib() is None or Z > 16):
        _native = None
    if _native is not None:
        flat_native = np.ascontiguousarray(
            np.concatenate([aux["flat_a"], aux["flat_b"]], axis=1))
        ezslot_native = aux["edge_zslot"].astype(np.int32)
        gzones_native = np.ascontiguousarray(
            aux["gzones64"].astype(np.int32))
    truth = _host_truth_fn(aux["polys"])

    def recheck(points64: np.ndarray, zone: np.ndarray,
                uncertain: np.ndarray) -> np.ndarray:
        sel = np.nonzero(uncertain)[0]
        if len(sel) == 0:
            return zone
        zone = np.asarray(zone).copy()
        pts = np.asarray(points64)[sel]
        face, a, b, margin = _host_lattice(aux["grid"], pts, idx.res,
                                           with_margin=True)
        out = _chip_zones(pts, face, a, b)
        # a point within the cell-edge sagitta band can sit in its H3
        # cell (true gnomonic edges) yet outside that cell's chips
        # (clipped against straight lon/lat chords): the original
        # polygons decide those
        band = margin < aux["sag_lattice"]
        if band.any():
            out[band] = truth(pts[band])
        zone[sel] = out
        return zone

    def _chip_zones(pts, face, a, b) -> np.ndarray:
        """Zone of each point by exact f64 parity against the chips of
        its host-lattice cell."""
        ia = a - idx.a0
        ib = b - idx.b0
        inw = ((face == idx.face0) & (ia >= 0) & (ia < idx.W) &
               (ib >= 0) & (ib < idx.H))
        e = np.where(inw, entry[np.where(inw, ia * idx.H + ib, 0)], -1)
        out = np.full(len(pts), -1, np.int32)
        is_core = (e >= 0) & ((e & int(CORE_FLAG)) != 0)
        out[is_core] = (e[is_core] & ~int(CORE_FLAG))

        isb = (e >= 0) & ~is_core
        bsel = np.nonzero(isb)[0]
        if len(bsel):
            # native chip-parity core when the C++ layer is available
            if _native is not None:
                grp = np.full(len(pts), -1, np.int64)
                grp[bsel] = e[bsel]
                nz = _native.recheck_zones(
                    pts, grp, flat_native, ezslot_native,
                    aux["gstart"], gzones_native)
                if nz is not None:
                    out[bsel] = nz[bsel]
                    return out
            g = e[bsel].astype(np.int64)
            gstart = aux["gstart"]
            cnt = (gstart[g + 1] - gstart[g]).astype(np.int64)
            total = int(cnt.sum())
            pidx = np.repeat(np.arange(len(bsel)), cnt)
            estart = np.repeat(gstart[g], cnt)
            local = np.arange(total) - np.repeat(
                np.concatenate([[0], np.cumsum(cnt)[:-1]]), cnt)
            eidx = estart + local
            pa = aux["flat_a"][eidx]
            pb = aux["flat_b"][eidx]
            zsl = aux["edge_zslot"][eidx]
            P = pts[bsel][pidx]
            ay, by = pa[:, 1], pb[:, 1]
            straddle = (ay <= P[:, 1]) != (by <= P[:, 1])
            denom = np.where(by == ay, 1.0, by - ay)
            xi = pa[:, 0] + (P[:, 1] - ay) / denom * (pb[:, 0] - pa[:, 0])
            crossed = straddle & (P[:, 0] < xi)
            counts = np.bincount(pidx * Z + zsl, weights=crossed,
                                 minlength=len(bsel) * Z)
            odd = (counts.reshape(len(bsel), Z).astype(np.int64) & 1)\
                .astype(bool)
            anyin = odd.any(axis=1)
            first = odd.argmax(axis=1)
            gz = aux["gzones64"][g, first]
            out[bsel[anyin]] = gz[anyin].astype(np.int32)
        return out

    return recheck


def pip_host_truth(points64: np.ndarray,
                   polys: GeometryArray) -> np.ndarray:
    """The exact float64 host oracle: first polygon containing each point
    (crossing-number, first-match tie-break) — the single source of truth
    that host_recheck, tests and bench all compare against.

    Routes through the native C++ kernel (mosaic_tpu.native, the
    JTS/GEOS-analogue layer) when the toolchain built it — bit-identical
    crossing rule — and falls back to the numpy broadcast loop."""
    return _host_truth_fn(polys)(points64)


def _host_truth_fn(polys: GeometryArray):
    """:func:`pip_host_truth` bound to ``polys``: the polygon edges are
    prepared once, so repeated calls on small point sets stay cheap."""
    from ..core.tessellate import _pip, _poly_edges
    edges_list = [_poly_edges(polys, gi) for gi in range(len(polys))]
    try:
        from .. import native
    except ImportError:
        native = None
    flat = gs = None
    if native is not None and len(polys):
        gs = np.zeros(len(polys) + 1, np.int64)
        np.cumsum([len(e) for e in edges_list], out=gs[1:])
        flat = np.concatenate(edges_list).reshape(-1, 4)

    def truth_of(points64: np.ndarray) -> np.ndarray:
        if flat is not None:
            # unavailability is signalled by None (no compiler); real
            # errors must raise, not silently fall back to the slow path
            out = native.pip_first_match(np.asarray(points64)[:, :2],
                                         flat, gs)
            if out is not None:
                return out
        truth = np.full(len(points64), -1, np.int32)
        for gi in range(len(polys)):
            inside = _pip(points64, edges_list[gi])
            truth = np.where((truth < 0) & inside, gi, truth)
        return truth

    return truth_of


def host_recheck(points64: np.ndarray, zone: np.ndarray,
                 uncertain: np.ndarray, polys: GeometryArray) -> np.ndarray:
    """Re-run the uncertain points in float64 against the original polygons
    (not the chips) on host — the exact tie-break authority."""
    sel = np.nonzero(uncertain)[0]
    if len(sel) == 0:
        return zone
    zone = zone.copy()
    zone[sel] = pip_host_truth(points64[sel], polys)
    return zone
