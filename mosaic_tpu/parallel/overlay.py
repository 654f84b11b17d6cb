"""Distributed polygon x polygon overlay join (P3): both sides sharded.

Reference mechanism: Spark hash-exchanges tessellated chips on cell id
(expressions/index/MosaicExplode.scala:70-79 feeding an equi-join), so
neither polygon set needs to fit on one executor.  SURVEY.md P3 names
the TPU-native equivalent: the equi-join becomes a cell-id-bucketed
all-to-all over ICI.

Pipeline (shard_map over the mesh's data axis):

  1. each device holds an arbitrary row-block of A-chips and B-chips
     (ingest placement);
  2. rows route to device hash(cell) % D via ONE jax.lax.all_to_all
     (fixed-capacity buckets: static shapes; overflow is counted and
     surfaced, never silently dropped);
  3. the local join is the sorted-table probe from the PIP join — sort
     local A rows by cell, binary-search each B row, probe duplicates;
  4. chip-pair ST_Intersects runs as dense f32 edge tests (segment
     crossings + representative-vertex containment);
  5. per-pair hits psum into a replicated [GA, GB] boolean matrix.

Exactness contract (same shape as pip_join): f32 hazards — near-touching
edges within EPS of crossing, or representative vertices within EPS of a
boundary — flag the pair; flagged pairs re-run on host in f64 against
the ORIGINAL geometries (overlay_host_pair).  ST_Intersects of two
polygons that merely share a tessellation cell but do not touch is
False, so the cell co-location is only the candidate filter, exactly as
in the reference.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ..core.geometry.array import GeometryArray
from ..core.index.base import IndexSystem
from ..core.tessellate import tessellate
from ..obs.context import traced
from ..resilience import faults
from ..types import ChipSet

EPS_DEG = 1e-6


# ----------------------------------------------------------- host packing

def pack_chip_rows(polys: GeometryArray, res: int, grid: IndexSystem,
                   chips: Optional[ChipSet] = None,
                   origin: Optional[np.ndarray] = None,
                   edge_cap: Optional[int] = None):
    """ChipSet -> dense device rows (cell i64, geom i32, edges [E, 4]
    f32 local, valid bool).

    Core chips carry the full cell boundary as their edge soup?  No —
    core cells are *fully covered* by their polygon, so for overlay
    purposes a core chip is the cell itself; tessellate(keep_core_geom
    =True) already emits the cell polygon for core chips."""
    if chips is None:
        chips = tessellate(polys, res, grid, keep_core_geom=True)
    from ..core.geometry.padded import build_edges_np
    A, B, M = build_edges_np(chips.geoms)
    if origin is None:
        bb = polys.bboxes()
        origin = np.round(np.array(
            [np.nanmean(bb[:, [0, 2]]), np.nanmean(bb[:, [1, 3]])]), 1)
    cap = edge_cap or A.shape[1]
    n, e = A.shape[:2]
    edges = np.full((n, cap, 4), 1e9, np.float32)
    e = min(e, cap)
    edges[:, :e, 0] = (A[:, :e, 0] - origin[0]).astype(np.float32)
    edges[:, :e, 1] = (A[:, :e, 1] - origin[1]).astype(np.float32)
    edges[:, :e, 2] = (B[:, :e, 0] - origin[0]).astype(np.float32)
    edges[:, :e, 3] = (B[:, :e, 1] - origin[1]).astype(np.float32)
    edges[~np.broadcast_to(M[:, :cap, None], edges.shape)] = 1e9
    valid = M[:, :cap].any(axis=1)
    assert M[:, cap:].sum() == 0, "edge_cap clipped real edges"
    return (chips.cell_id.astype(np.int64),
            chips.geom_id.astype(np.int32), edges, valid, origin, chips)


def _pad_rows(cell, ids, edges, valid, rows_per_dev: int, n_dev: int):
    """Round-robin row-block placement padded to [n_dev*rows_per_dev].
    ``ids`` keeps its dtype (int32 geom ids or int64 row ids)."""
    n = len(cell)
    total = rows_per_dev * n_dev
    assert n <= total, (n, total)
    pad = total - n
    cell = np.concatenate([cell, np.full(pad, -1, np.int64)])
    ids = np.concatenate([ids, np.full(pad, -1, ids.dtype)])
    edges = np.concatenate(
        [edges, np.full((pad, *edges.shape[1:]), 1e9, np.float32)])
    valid = np.concatenate([valid, np.zeros(pad, bool)])
    return cell, ids, edges, valid


# ----------------------------------------------------------- device logic

def _hash_dest(cell, n_dev: int):
    """Cheap int64 mix -> device index (valid rows only)."""
    import jax.numpy as jnp
    mix = np.uint64(0x9E3779B97F4A7C15).astype(np.int64)  # wraps signed
    h = cell * jnp.int64(mix)
    h = h ^ (h >> 29)
    return (h % n_dev + n_dev).astype(jnp.int32) % n_dev


def _hash_dest_np(cell: np.ndarray, n_dev: int) -> np.ndarray:
    """Host mirror of _hash_dest (same int64 wraparound semantics) —
    lets callers size the exchange buckets EXACTLY before compiling,
    so hash skew never triggers the double-capacity re-jit loop
    (VERDICT round-3 weak #5)."""
    mix = np.uint64(0x9E3779B97F4A7C15).astype(np.int64)
    with np.errstate(over="ignore"):
        h = np.asarray(cell, np.int64) * mix
    h = h ^ (h >> 29)
    return ((h % n_dev + n_dev) % n_dev).astype(np.int32)


def _exact_bucket_cap(cells: np.ndarray, valid: np.ndarray,
                      n_dev: int) -> int:
    """Exact per-device row count maximum for the exchange."""
    if not valid.any():
        return 64
    d = _hash_dest_np(cells[valid], n_dev)
    return max(64, int(np.bincount(d, minlength=n_dev).max()))


def _account_exchange(site: str, D: int, bucket_cap: int, cap_e: int,
                      id_bytes: int, cells: np.ndarray,
                      valid: np.ndarray) -> None:
    """Host-side collective accounting for one `_exchange_rows` run.

    Bytes come from the static send-buffer shapes each device pushes
    through the four all_to_alls (per row: cell i64 + id column +
    [cap_e, 4] f32 edges + valid bool; D*bucket_cap rows per device, D
    devices); shard skew is max/mean of the exact host-side hash
    destination counts (`_hash_dest_np` mirrors the device hash).  One
    attribute check when metrics are disabled."""
    from ..obs import metrics
    if not metrics.enabled:
        return
    row_bytes = 8 + id_bytes + cap_e * 16 + 1
    moved = float(D) * D * bucket_cap * row_bytes
    metrics.count("collective/all_to_all_bytes", moved)
    metrics.count(f"collective/all_to_all_bytes/{site}", moved)
    metrics.count("collective/all_to_all_calls", 4)
    v = np.asarray(valid, bool)
    if v.any():
        counts = np.bincount(_hash_dest_np(np.asarray(cells)[v], D),
                             minlength=D)
        mean = float(counts.mean())
        skew = float(counts.max()) / mean if mean else 1.0
        metrics.gauge(f"shard/skew/{site}", skew)
        # also a distribution so repeated exchanges build a time
        # series (p50/p95/p99), not just a last-value gauge
        metrics.observe(f"shard/skew_series/{site}", skew)
        metrics.gauge(f"shard/rows_max/{site}", float(counts.max()))
        # per-device fold: the exchange's routed-row counts land in
        # the device monitor (device/rows/* counters + dashboard)
        from ..obs.devicemon import devicemon
        devicemon.observe_rows(site, counts)


def _exact_dup_cap(cells_a: np.ndarray, valid_a: np.ndarray,
                   cells_b: np.ndarray, valid_b: np.ndarray) -> int:
    """Exact probe width: the max chip multiplicity among A cells that
    are actually PROBED (cells also present on the B side — sizing on
    all A cells over-ran the dup loop ~3x on the overlay bench)."""
    if not valid_a.any() or not valid_b.any():
        return 1
    ca = cells_a[valid_a]
    probed = np.isin(ca, cells_b[valid_b])
    if not probed.any():
        return 1
    _, counts = np.unique(ca[probed], return_counts=True)
    return max(1, int(counts.max()))


def _chip_pair_test(ea, eb, eps=EPS_DEG):
    """f32 intersects + hazard flag for one chip pair.

    ea, eb [E, 4] (ax, ay, bx, by; 1e9 sentinel padding).  Returns
    (hit, hazard).  hit = any proper segment crossing, or a
    representative vertex of one inside the other (if no edges cross,
    the chips are disjoint or nested — one containment test each way
    decides).  hazard = any orientation test or containment crossing
    within ``eps`` (absolute degrees; the caller scales it with the
    local-frame extent so it always covers f32 coordinate
    quantization)."""
    import jax.numpy as jnp

    a1 = ea[:, None, 0:2]
    b1 = ea[:, None, 2:4]
    a2 = eb[None, :, 0:2]
    b2 = eb[None, :, 2:4]

    def orient(p, q, r):
        return (q[..., 0] - p[..., 0]) * (r[..., 1] - p[..., 1]) - \
               (q[..., 1] - p[..., 1]) * (r[..., 0] - p[..., 0])

    d1 = orient(a2, b2, a1)
    d2 = orient(a2, b2, b1)
    d3 = orient(a1, b1, a2)
    d4 = orient(a1, b1, b2)
    pad = (jnp.abs(ea[:, None, 0]) > 1e8) | \
        (jnp.abs(eb[None, :, 0]) > 1e8)
    proper = ((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0)) & ~pad
    # hazard band: an endpoint within EPS_DEG (absolute degrees) of the
    # other segment's line — |orient|/len(other) IS that perpendicular
    # distance.  (A len1*len2 normalization made the band proportional
    # to edge length: a ~100 m footprint edge got a 5e-10 deg band and a
    # real f32 miscall shipped unflagged — caught by the bench's
    # overlay parity check.)
    l1 = jnp.maximum(jnp.linalg.norm(b1 - a1, axis=-1), 1e-30)
    l2 = jnp.maximum(jnp.linalg.norm(b2 - a2, axis=-1), 1e-30)
    tiny = ((jnp.minimum(jnp.abs(d1), jnp.abs(d2)) / l2 < eps) |
            (jnp.minimum(jnp.abs(d3), jnp.abs(d4)) / l1 < eps)) & \
        ~pad
    crossing = jnp.any(proper)

    def contains(point, e):
        px, py = point[0], point[1]
        ax, ay, bx, by = e[:, 0], e[:, 1], e[:, 2], e[:, 3]
        epad = jnp.abs(ax) > 1e8
        straddle = ((ay <= py) != (by <= py)) & ~epad
        t = (py - ay) / jnp.where(by == ay, 1.0, by - ay)
        xi = ax + t * (bx - ax)
        hits = straddle & (px < xi)
        inside = (jnp.sum(hits) & 1).astype(bool)
        near = jnp.any(straddle & (jnp.abs(px - xi) < eps)) | \
            jnp.any((jnp.abs(py - ay) < eps) & ~epad &
                    (px < jnp.maximum(ax, bx) + eps))
        return inside, near

    ina, na = contains(ea[0, 0:2], eb)
    inb, nb = contains(eb[0, 0:2], ea)
    hit = crossing | ina | inb
    hazard = jnp.any(tiny) | na | nb
    return hit, hazard


def _local_sorted_join(cell_a, geom_a, edges_a, valid_a,
                       cell_b, geom_b, edges_b, valid_b,
                       ga: int, gb: int, dup_cap: int,
                       eps: float = EPS_DEG):
    """Sorted-table probe join of local rows; returns (hits [ga, gb]
    i32, hazards [ga, gb] i32, max_dup_needed)."""
    import jax
    import jax.numpy as jnp

    big = jnp.int64(0x7FFFFFFFFFFFFFFF)
    key_a = jnp.where(valid_a, cell_a, big)
    order = jnp.argsort(key_a)
    key_a = key_a[order]
    geom_a = geom_a[order]
    edges_a = edges_a[order]

    start = jnp.searchsorted(key_a, jnp.where(valid_b, cell_b, -big))
    upper = jnp.searchsorted(key_a, jnp.where(valid_b, cell_b, -big),
                             side="right")
    dup_needed = jnp.max(jnp.where(valid_b, upper - start, 0))

    pair_fn = jax.vmap(
        lambda ea, eb: _chip_pair_test(ea, eb, jnp.float32(eps)))
    na = key_a.shape[0]

    # duplicate probe as a fori_loop: program size stays constant when
    # crowded cells force dup_cap up (an unrolled python loop re-traced
    # thousands of pair-test vmaps at dup_cap retries)
    def body(j, carry):
        hits, hazards = carry
        s = jnp.clip(start + j, 0, max(na - 1, 0))
        match = valid_b & (start + j < upper)
        h, hz = pair_fn(edges_a[s], edges_b)
        ga_i = jnp.where(match, geom_a[s], 0)
        gb_i = jnp.where(match, geom_b, 0)
        add_h = (h & match).astype(jnp.int32)
        add_z = (hz & match).astype(jnp.int32)
        hits = hits.at[ga_i, gb_i].max(add_h, mode="drop")
        hazards = hazards.at[ga_i, gb_i].max(add_z, mode="drop")
        return hits, hazards

    # under shard_map the carry must already be device-varying (the loop
    # body's scatters are), so seed it with a varying zero
    zero = (cell_b[:1].astype(jnp.int32) * 0).reshape(())
    init = jnp.zeros((ga, gb), jnp.int32) + zero
    hits, hazards = jax.lax.fori_loop(0, dup_cap, body, (init, init))
    return hits, hazards, dup_needed


def make_overlay_fn(ga: int, gb: int, edge_cap_a: int, edge_cap_b: int,
                    mesh=None, axis: str = "data",
                    bucket_cap: int = 0, dup_cap: int = 8,
                    eps: float = EPS_DEG):
    """Build the (optionally sharded) overlay ST_Intersects kernel.

    Returns fn(cell_a, geom_a, edges_a, valid_a, cell_b, ...) ->
    (hits [ga, gb] i32, hazards [ga, gb] i32, diag [3] i32 =
    (overflow_a, overflow_b, dup_needed)).  Without a mesh it is the
    single-device join (no exchange); with a mesh, rows all_to_all to
    hash(cell) % D first."""
    import jax
    import jax.numpy as jnp

    from ..perf.jit_cache import kernel_cache

    if mesh is None:
        def fn(ca, gea, ea, va, cb, geb, eb, vb):
            h, z, dn = _local_sorted_join(ca, gea, ea, va, cb, geb, eb,
                                          vb, ga, gb, dup_cap, eps)
            return h, z, jnp.stack([jnp.int32(0), jnp.int32(0),
                                    dn.astype(jnp.int32)])
        return kernel_cache.get_or_build(
            "overlay/dense", (ga, gb, dup_cap, eps),
            lambda: jax.jit(fn))

    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    D = mesh.shape[axis]
    assert bucket_cap > 0, "sharded overlay needs a bucket capacity"

    def local(ca, gea, ea, va, cb, geb, eb, vb):
        ca, gea, ea, va, ofa = _exchange_rows(
            ca, gea, ea, va, D, axis, bucket_cap, edge_cap_a)
        cb, geb, eb, vb, ofb = _exchange_rows(
            cb, geb, eb, vb, D, axis, bucket_cap, edge_cap_b)
        h, z, dn = _local_sorted_join(ca, gea, ea, va, cb, geb, eb, vb,
                                      ga, gb, dup_cap, eps)
        diag = jnp.stack([ofa.astype(jnp.int32), ofb.astype(jnp.int32),
                          dn.astype(jnp.int32)])
        return (jax.lax.psum(h, axis), jax.lax.psum(z, axis),
                jax.lax.pmax(diag, axis))

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P()))
    # id(mesh): same-shaped kernels on different meshes must not alias
    return kernel_cache.get_or_build(
        "overlay/dense_sharded",
        (ga, gb, edge_cap_a, edge_cap_b, id(mesh), axis, bucket_cap,
         dup_cap, eps),
        lambda: jax.jit(fn))


# ----------------------------------------------------- ragged pair output

def _compact_keys(keys, cap: int):
    """[M] int64 keys (-1 invalid) -> ([cap] desc-sorted keys, count,
    overflow).  Fixed capacity + overflow count: the same
    never-silently-drop discipline as the exchange buckets."""
    import jax.numpy as jnp
    valid = keys >= 0
    total = jnp.sum(valid)
    srt = jnp.sort(keys)[::-1]
    return srt[:cap], jnp.minimum(total, cap), \
        jnp.maximum(total - cap, 0)


def _local_pair_join(cell_a, row_a, edges_a, valid_a,
                     cell_b, row_b, edges_b, valid_b,
                     row_mult: int, dup_cap: int, pair_cap: int,
                     eps: float):
    """Sorted-table probe join emitting (hit|hazard) ROW pairs as a
    compacted key list instead of scattering into a dense matrix
    (VERDICT round-3 missing #4: the replicated [GA, GB] psum cannot
    scale to millions of footprints).  Key = row_a * row_mult + row_b
    over GLOBAL chip row ids; the caller maps rows to geometries or
    chip edges.  Returns (keys [pair_cap], count, overflow,
    dup_needed)."""
    import jax
    import jax.numpy as jnp

    big = jnp.int64(0x7FFFFFFFFFFFFFFF)
    key_a = jnp.where(valid_a, cell_a, big)
    order = jnp.argsort(key_a)
    key_a = key_a[order]
    row_a = row_a[order]
    edges_a = edges_a[order]

    probe = jnp.where(valid_b, cell_b, -big)
    start = jnp.searchsorted(key_a, probe)
    upper = jnp.searchsorted(key_a, probe, side="right")
    dup_needed = jnp.max(jnp.where(valid_b, upper - start, 0))

    pair_fn = jax.vmap(
        lambda ea, eb: _chip_pair_test(ea, eb, jnp.float32(eps)))
    na = key_a.shape[0]
    nb = cell_b.shape[0]

    def body(j, buf):
        s = jnp.clip(start + j, 0, max(na - 1, 0))
        match = valid_b & (start + j < upper)
        h, hz = pair_fn(edges_a[s], edges_b)
        emit = match & (h | hz)
        keys = jnp.where(
            emit, row_a[s] * jnp.int64(row_mult) + row_b,
            jnp.int64(-1))
        return jax.lax.dynamic_update_slice(buf, keys, (j * nb,))

    zero = (cell_b[:1] * 0).reshape(())     # device-varying seed
    buf = jnp.full((dup_cap * nb,), jnp.int64(-1)) + zero
    buf = jax.lax.fori_loop(0, dup_cap, body, buf)
    keys, count, overflow = _compact_keys(buf, pair_cap)
    return keys, count, overflow, dup_needed


def make_overlay_pairs_fn(row_mult: int, edge_cap_a: int,
                          edge_cap_b: int, mesh=None,
                          axis: str = "data", bucket_cap: int = 0,
                          dup_cap: int = 8, pair_cap: int = 0,
                          eps: float = EPS_DEG):
    """Build the pair-emitting overlay join kernel.

    fn(cell_a, row_a, edges_a, valid_a, cell_b, row_b, edges_b,
    valid_b) -> (keys, count, overflow_diag).  Without a mesh: one
    device, keys [pair_cap].  With a mesh: rows all_to_all to
    hash(cell) % D, each device emits its own compacted key block
    (out_specs sharded — NO replicated matrix, NO psum), and the diag
    carries (bucket_overflow_a, bucket_overflow_b, dup_needed,
    pair_overflow) maxed across devices."""
    import jax
    import jax.numpy as jnp

    from ..perf.jit_cache import kernel_cache

    assert pair_cap > 0
    if mesh is None:
        def fn(ca, ra, ea, va, cb, rb, eb, vb):
            keys, count, ovf, dn = _local_pair_join(
                ca, ra, ea, va, cb, rb, eb, vb, row_mult, dup_cap,
                pair_cap, eps)
            diag = jnp.stack([jnp.int32(0), jnp.int32(0),
                              dn.astype(jnp.int32),
                              ovf.astype(jnp.int32)])
            return keys, count[None], diag
        return kernel_cache.get_or_build(
            "overlay/pairs", (row_mult, dup_cap, pair_cap, eps),
            lambda: jax.jit(fn))

    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    D = mesh.shape[axis]
    assert bucket_cap > 0

    def local(ca, ra, ea, va, cb, rb, eb, vb):
        ca, ra, ea, va, ofa = _exchange_rows(
            ca, ra, ea, va, D, axis, bucket_cap, edge_cap_a)
        cb, rb, eb, vb, ofb = _exchange_rows(
            cb, rb, eb, vb, D, axis, bucket_cap, edge_cap_b)
        keys, count, ovf, dn = _local_pair_join(
            ca, ra, ea, va, cb, rb, eb, vb, row_mult, dup_cap,
            pair_cap, eps)
        diag = jnp.stack([ofa.astype(jnp.int32), ofb.astype(jnp.int32),
                          dn.astype(jnp.int32), ovf.astype(jnp.int32)])
        return keys, count[None], jax.lax.pmax(diag, axis)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis),) * 8,
        out_specs=(P(axis), P(axis), P()))
    # id(mesh): same-shaped kernels on different meshes must not alias
    return kernel_cache.get_or_build(
        "overlay/pairs_sharded",
        (row_mult, edge_cap_a, edge_cap_b, id(mesh), axis, bucket_cap,
         dup_cap, pair_cap, eps),
        lambda: jax.jit(fn))


def _exchange_rows(cell, row, edges, valid, D: int, axis: str,
                   bucket_cap: int, cap_e: int):
    """all_to_all row exchange keyed on hash(cell) % D, carrying one
    id column (geom ids or global row ids — dtype preserved).  The
    single exchange implementation behind both the dense-matrix and
    the pair-emitting overlay paths."""
    import jax
    import jax.numpy as jnp
    dest = jnp.where(valid, _hash_dest(cell, D), D)
    order = jnp.argsort(dest)
    dest_s = dest[order]
    pos = jnp.arange(dest.shape[0], dtype=jnp.int32) - \
        jnp.searchsorted(dest_s, dest_s).astype(jnp.int32)
    overflow = jnp.sum((pos >= bucket_cap) & (dest_s < D))
    okrow = (dest_s < D) & (pos < bucket_cap)
    # bad rows route to device index D: out of bounds, so the
    # mode="drop" scatters discard them instead of clobbering the
    # last in-bounds slot
    d_i = jnp.where(okrow, dest_s, D)
    p_i = jnp.where(okrow, pos, 0)
    sc = jnp.full((D, bucket_cap), jnp.int64(-1))
    sr = jnp.full((D, bucket_cap), jnp.asarray(-1, row.dtype))
    se = jnp.full((D, bucket_cap, cap_e, 4), jnp.float32(1e9))
    sv = jnp.zeros((D, bucket_cap), bool)
    sc = sc.at[d_i, p_i].set(jnp.where(okrow, cell[order], -1),
                             mode="drop")
    sr = sr.at[d_i, p_i].set(jnp.where(okrow, row[order], -1),
                             mode="drop")
    se = se.at[d_i, p_i].set(jnp.where(okrow[:, None, None],
                                       edges[order], 1e9), mode="drop")
    sv = sv.at[d_i, p_i].set(okrow & valid[order], mode="drop")
    rc = jax.lax.all_to_all(sc, axis, 0, 0)
    rr = jax.lax.all_to_all(sr, axis, 0, 0)
    re = jax.lax.all_to_all(se, axis, 0, 0)
    rv = jax.lax.all_to_all(sv, axis, 0, 0)
    flat = lambda x: x.reshape((D * bucket_cap,) + x.shape[2:])
    return flat(rc), flat(rr), flat(re), flat(rv), overflow


@traced("overlay", "overlay/row_pairs")
def overlay_row_pairs(chips_a, chips_b, polys_a: GeometryArray,
                      polys_b: GeometryArray, res: int,
                      grid: IndexSystem, mesh=None,
                      axis: str = "data",
                      origin: Optional[np.ndarray] = None):
    """Distributed chip-row pair discovery: all (rowA, rowB) chip pairs
    that share a cell and (possibly) touch, as a ragged host list.

    Returns (rows_a [K], rows_b [K]) global chip-row indices.  Memory
    is bounded per device (capacity + overflow retry); the dense
    [GA, GB] matrix never materializes."""
    import jax.numpy as jnp

    ra = pack_chip_rows(polys_a, res, grid, chips=chips_a,
                        origin=origin)
    origin = ra[4]
    rb = pack_chip_rows(polys_b, res, grid, chips=chips_b,
                        origin=origin)
    ca, _, ea, va = ra[:4]
    cb, _, eb, vb = rb[:4]
    rowa = np.arange(len(ca), dtype=np.int64)
    rowb = np.arange(len(cb), dtype=np.int64)
    row_mult = int(len(cb)) + 1
    ext = 1.0
    for arr in (ea, eb):
        fin = arr[np.abs(arr) < 1e8]
        if len(fin):
            ext = max(ext, float(np.abs(fin).max()))
    eps = max(EPS_DEG, 64.0 * float(np.spacing(np.float32(ext))))

    dup_cap = faults.degrade("overlay.dup_cap",
                             _exact_dup_cap(ca, va, cb, vb))
    if mesh is not None:
        D = mesh.shape[axis]
        rpa = -(-len(ca) // D)
        rpb = -(-len(cb) // D)
        bucket_cap = faults.degrade(
            "overlay.bucket_cap",
            max(_exact_bucket_cap(ca, va, D),
                _exact_bucket_cap(cb, vb, D)))
        ca, rowa, ea, va = _pad_rows(ca, rowa, ea, va, rpa, D)
        cb, rowb, eb, vb = _pad_rows(cb, rowb, eb, vb, rpb, D)
        pair_cap = max(1024, 4 * max(rpa, rpb))
    else:
        pair_cap = max(1024, 4 * len(ca))
    args = tuple(jnp.asarray(v) for v in
                 (ca, rowa, ea, va, cb, rowb, eb, vb))
    while True:
        if mesh is None:
            fn = make_overlay_pairs_fn(
                row_mult, ea.shape[1], eb.shape[1], dup_cap=dup_cap,
                pair_cap=pair_cap, eps=eps)
        else:
            fn = make_overlay_pairs_fn(
                row_mult, ea.shape[1], eb.shape[1], mesh=mesh,
                axis=axis, bucket_cap=bucket_cap, dup_cap=dup_cap,
                pair_cap=pair_cap, eps=eps)
            _account_exchange("overlay_pairs", D, bucket_cap,
                              ea.shape[1], 8, ca, va)
            _account_exchange("overlay_pairs", D, bucket_cap,
                              eb.shape[1], 8, cb, vb)
        keys, counts, diag = fn(*args)
        diag = np.asarray(diag)
        if mesh is not None and (diag[0] > 0 or diag[1] > 0):
            bucket_cap *= 2
            continue
        if diag[2] > dup_cap:
            dup_cap = int(2 ** np.ceil(np.log2(max(diag[2], 2))))
            continue
        if diag[3] > 0:
            pair_cap *= 2
            continue
        break
    keys = np.asarray(keys).reshape(-1)
    counts = np.asarray(counts).reshape(-1)
    if mesh is None:
        valid = keys[:int(counts[0])]
    else:
        blocks = keys.reshape(len(counts), -1)
        valid = np.concatenate([blocks[d, :int(counts[d])]
                                for d in range(len(counts))])
    valid = np.unique(valid)
    return valid // row_mult, valid % row_mult


@traced("overlay", "overlay/intersection_area")
def overlay_intersection_area(polys_a: GeometryArray,
                              polys_b: GeometryArray, res: int,
                              grid: IndexSystem, mesh=None,
                              axis: str = "data"):
    """Distributed exact ST_IntersectionAgg AREA: for every
    intersecting polygon pair, the planar area of the intersection.

    Mechanism (reference: tessellate + equi-join on cell id feeding
    ST_IntersectionAgg, MosaicExplode.scala:70-79 +
    ST_IntersectionAgg.scala:41-58): chips partition each polygon
    within each cell, so area(A∩B) = Σ over shared cells of
    area(chipA ∩ chipB).  The sharded join emits candidate chip-row
    pairs (ragged, capacity-bounded); the exact per-pair areas run
    through the native fragment-shoelace kernel
    (clip.pairs_intersection_area), and a segment-sum folds them into
    per-(geomA, geomB) totals.

    Returns (ga [K], gb [K], area [K]) for pairs with area > 0."""
    from ..core.geometry.clip import pairs_intersection_area
    chips_a = tessellate(polys_a, res, grid, keep_core_geom=True)
    chips_b = tessellate(polys_b, res, grid, keep_core_geom=True)
    rows_a, rows_b = overlay_row_pairs(chips_a, chips_b, polys_a,
                                       polys_b, res, grid, mesh, axis)
    areas = pairs_intersection_area(chips_a.geoms, rows_a,
                                    chips_b.geoms, rows_b)
    ga = chips_a.geom_id[rows_a].astype(np.int64)
    gb = chips_b.geom_id[rows_b].astype(np.int64)
    mult = int(chips_b.geom_id.max(initial=0)) + 1
    key = ga * mult + gb
    uk, inv = np.unique(key, return_inverse=True)
    tot = np.zeros(len(uk))
    np.add.at(tot, inv, areas)
    keep = tot > 0
    return (uk[keep] // mult, uk[keep] % mult, tot[keep])


# ------------------------------------------------------------ host oracle

def overlay_host_pair(polys_a: GeometryArray, polys_b: GeometryArray,
                      ia: int, ib: int) -> bool:
    """Exact f64 ST_Intersects of one polygon pair (edge crossings +
    mutual containment via crossing number)."""
    from ..core.tessellate import _pip, _poly_edges, _seg_cross
    ea = _poly_edges(polys_a, ia)
    eb = _poly_edges(polys_b, ib)
    if len(ea) == 0 or len(eb) == 0:
        return False
    if np.any(_seg_cross(ea[:, None, 0], ea[:, None, 1],
                         eb[None, :, 0], eb[None, :, 1])):
        return True
    return bool(_pip(ea[:1, 0], eb)[0] or _pip(eb[:1, 0], ea)[0])


def overlay_host_truth(polys_a: GeometryArray,
                       polys_b: GeometryArray) -> np.ndarray:
    """[GA, GB] exact boolean intersects matrix (bbox-pruned)."""
    ba = polys_a.bboxes()
    bb = polys_b.bboxes()
    out = np.zeros((len(polys_a), len(polys_b)), bool)
    for i in range(len(polys_a)):
        cand = np.nonzero((ba[i, 0] <= bb[:, 2]) & (bb[:, 0] <= ba[i, 2])
                          & (ba[i, 1] <= bb[:, 3]) &
                          (bb[:, 1] <= ba[i, 3]))[0]
        for j in cand:
            out[i, j] = overlay_host_pair(polys_a, polys_b, i, int(j))
    return out


# -------------------------------------------------------------- end2end

@traced("overlay", "overlay/intersects")
def overlay_intersects(polys_a: GeometryArray, polys_b: GeometryArray,
                       res: int, grid: IndexSystem, mesh=None,
                       axis: str = "data") -> np.ndarray:
    """Distributed exact ST_Intersects overlay: [GA, GB] bool.

    Tessellates both sides, runs the (sharded) chip join, then resolves
    f32-hazard pairs on host in f64.  This is the BASELINE config 3
    (building footprints x flood zones) engine."""
    import jax.numpy as jnp

    rows_a = pack_chip_rows(polys_a, res, grid)
    origin = rows_a[4]
    rows_b = pack_chip_rows(polys_b, res, grid, origin=origin)
    ca, gea, ea, va = rows_a[:4]
    cb, geb, eb, vb = rows_b[:4]
    ga, gb = len(polys_a), len(polys_b)
    # hazard band scaled with the local-frame extent: f32 quantization
    # of a coordinate of magnitude m displaces vertices by ~ulp(m), so
    # a fixed 1e-6 band under-flags continent-scale inputs
    ext = 1.0
    for arr in (ea, eb):
        fin = arr[np.abs(arr) < 1e8]
        if len(fin):
            ext = max(ext, float(np.abs(fin).max()))
    eps = max(EPS_DEG, 64.0 * float(np.spacing(np.float32(ext))))

    dup_cap = faults.degrade("overlay.dup_cap",
                             _exact_dup_cap(ca, va, cb, vb))
    if mesh is not None:
        D = mesh.shape[axis]
        rpa = -(-len(ca) // D)
        rpb = -(-len(cb) // D)
        # size the exchange exactly from the host-computed hash — no
        # overflow retry/recompile is possible for buckets or dups
        # (unless a chaos plan degrades the capacity on purpose, which
        # exercises the overflow-retry loop below)
        bucket_cap = faults.degrade(
            "overlay.bucket_cap",
            max(_exact_bucket_cap(ca, va, D),
                _exact_bucket_cap(cb, vb, D)))
        ca, gea, ea, va = _pad_rows(ca, gea, ea, va, rpa, D)
        cb, geb, eb, vb = _pad_rows(cb, geb, eb, vb, rpb, D)
    args = tuple(jnp.asarray(v) for v in
                 (ca, gea, ea, va, cb, geb, eb, vb))
    # retry loops: bucket/dup capacities are static shapes, so a skewed
    # hash or a crowded cell grows them and re-runs instead of failing
    # (overflow is always detected, never silent)
    import time as _time
    t0 = _time.perf_counter()
    while True:
        if mesh is None:
            fn = make_overlay_fn(ga, gb, ea.shape[1], eb.shape[1],
                                 dup_cap=dup_cap, eps=eps)
        else:
            fn = make_overlay_fn(ga, gb, ea.shape[1], eb.shape[1],
                                 mesh=mesh, axis=axis,
                                 bucket_cap=bucket_cap, dup_cap=dup_cap,
                                 eps=eps)
            _account_exchange("overlay", D, bucket_cap, ea.shape[1], 4,
                              ca, va)
            _account_exchange("overlay", D, bucket_cap, eb.shape[1], 4,
                              cb, vb)
        h, z, diag = fn(*args)
        diag = np.asarray(diag)
        if mesh is not None and (diag[0] > 0 or diag[1] > 0):
            bucket_cap *= 2
            continue
        if diag[2] > dup_cap:
            dup_cap = int(2 ** np.ceil(np.log2(max(diag[2], 2))))
            continue
        break
    from ..obs import metrics
    if mesh is not None and metrics.enabled:
        # charge the sharded run's wall time to devices by routed-row
        # share (both sides' hash-destination counts) — feeds the
        # EXPLAIN ANALYZE device_ms column via obs.devicemon
        from ..obs.devicemon import devicemon
        w = np.zeros(D, np.int64)
        for cc, vv in ((ca, va), (cb, vb)):
            vv = np.asarray(vv, bool)
            if vv.any():
                w += np.bincount(
                    _hash_dest_np(np.asarray(cc)[vv], D), minlength=D)
        devicemon.attribute("overlay", _time.perf_counter() - t0, w)

    hits = np.asarray(h) > 0
    hz = np.asarray(z) > 0
    # f64 resolution of flagged pairs against the ORIGINAL geometries
    for i, j in zip(*np.nonzero(hz)):
        hits[i, j] = overlay_host_pair(polys_a, polys_b, int(i), int(j))
    return hits
