"""Zone sets of the benchmark's configurations, as plain rings.

A copy of the program's generators (``mosaic_tpu/bench/workloads.py``:
``taxi_zones``, ``conus_counties``) that produces numpy rings and
imports nothing of the program, so the yardstick stays put when the
program changes.  A zone set is a list of zones; a zone is a list of
parts; a part is a list of closed ``[V, 2]`` float64 rings, shell
first, holes after.

``load(spec, cache_dir)`` serves a zone set from a per-checkout file
once the generator has made it: the generator is deterministic, and
users load their zones from files too.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, Sequence

import numpy as np

Ring = np.ndarray
Zone = List[List[Ring]]


def proper_crossings(e1: np.ndarray, e2: np.ndarray) -> np.ndarray:
    """[N, M] bool: strict interior crossing of each segment pair."""
    a1, b1 = e1[:, None, 0], e1[:, None, 1]
    a2, b2 = e2[None, :, 0], e2[None, :, 1]

    def orient(p, q, r):
        return (q[..., 0] - p[..., 0]) * (r[..., 1] - p[..., 1]) - \
               (q[..., 1] - p[..., 1]) * (r[..., 0] - p[..., 0])

    d1 = orient(a2, b2, a1)
    d2 = orient(a2, b2, b1)
    d3 = orient(a1, b1, a2)
    d4 = orient(a1, b1, b2)
    return ((d1 > 0) != (d2 > 0)) & ((d3 > 0) != (d4 > 0)) & \
        (d1 != 0) & (d2 != 0) & (d3 != 0) & (d4 != 0)


def _pip_rings(points: np.ndarray, rings: Sequence[np.ndarray]) -> np.ndarray:
    """Even-odd membership of points in the region bounded by ``rings``."""
    inside = np.zeros(len(points), bool)
    px = points[:, 0][:, None]
    py = points[:, 1][:, None]
    for r in rings:
        r = np.asarray(r, np.float64)[:, :2]
        if len(r) >= 2 and np.array_equal(r[0], r[-1]):
            r = r[:-1]
        if len(r) < 3:
            continue
        ax, ay = r[:, 0][None], r[:, 1][None]
        bx = np.concatenate([r[1:, 0], r[:1, 0]])[None]
        by = np.concatenate([r[1:, 1], r[:1, 1]])[None]
        straddle = (ay <= py) != (by <= py)
        with np.errstate(divide="ignore", invalid="ignore"):
            t = (py - ay) / np.where(by == ay, 1.0, by - ay)
        xi = ax + t * (bx - ax)
        inside ^= ((straddle & (px < xi)).sum(axis=1) & 1).astype(bool)
    return inside


def _seg_point_dist(points: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Min distance from each point to any edge ([N] float64)."""
    a = edges[None, :, 0]
    b = edges[None, :, 1]
    ab = b - a
    ap = points[:, None, :] - a
    denom = np.sum(ab * ab, axis=-1)
    t = np.clip(np.sum(ap * ab, axis=-1) / np.where(denom == 0, 1.0, denom),
                0.0, 1.0)
    proj = a + t[..., None] * ab
    d = points[:, None, :] - proj
    return np.sqrt(np.min(np.sum(d * d, axis=-1), axis=1))


def _wiggle(p0, p1, rng, levels: int = 2, amp: float = 0.22) -> np.ndarray:
    """Midpoint-displacement polyline from p0 to p1 (endpoints fixed)."""
    return _displace(np.array([p0, p1], dtype=np.float64), rng, levels, amp)


def _detail(pts, rng, split: int, levels: int, amp: float) -> np.ndarray:
    """Finer border: each segment of ``pts`` cut into ``split`` straight
    pieces, then ``levels`` more rounds of midpoint displacement."""
    t = np.arange(split)[None, :, None] / split
    fine = pts[:-1, None] + t * (pts[1:] - pts[:-1])[:, None]
    return _displace(np.vstack([fine.reshape(-1, 2), pts[-1:]]), rng,
                     levels, amp)


def _displace(pts, rng, levels: int, amp: float) -> np.ndarray:
    for _ in range(levels):
        seg = pts[1:] - pts[:-1]
        mid = (pts[:-1] + pts[1:]) / 2
        perp = np.stack([-seg[:, 1], seg[:, 0]], axis=-1)
        mid = mid + perp * rng.uniform(-amp, amp, (len(mid), 1))
        out = np.empty((len(pts) + len(mid), 2))
        out[0::2] = pts
        out[1::2] = mid
        pts = out
    return pts


def _fit_hole(ring, corner_nodes, pitch_x, pitch_y):
    """Largest of a few candidate hole squares strictly inside ``ring``."""
    c = corner_nodes.mean(axis=0)
    closed = np.vstack([ring, ring[:1]])
    edges = np.stack([closed[:-1], closed[1:]], axis=1)
    margin = 0.02 * min(pitch_x, pitch_y)
    for scale in (0.16, 0.12, 0.08, 0.05):
        hw, hh = pitch_x * scale, pitch_y * scale
        sq = np.array([[c[0] - hw, c[1] - hh], [c[0] + hw, c[1] - hh],
                       [c[0] + hw, c[1] + hh], [c[0] - hw, c[1] + hh],
                       [c[0] - hw, c[1] - hh]])
        hole_edges = np.stack([sq[:-1], sq[1:]], axis=1)
        if np.all(_pip_rings(sq[:4], [ring])) and \
                _seg_point_dist(sq[:4], edges).min() > margin and \
                not np.any(proper_crossings(hole_edges, edges)):
            return sq
    return None


def taxi_zones(n_side: int, seed: int, bbox: Sequence[float],
               hole_every: int, merge_every: int,
               detail: Sequence[int] = (1, 0)) -> List[Zone]:
    """Planar partition of ``bbox`` into concave multipolygon zones with
    holes: jittered lattice, shared fractal edges, every
    ``hole_every``-th cell holed (the hole emitted as an island zone),
    every ``merge_every``-th pair of far-apart cells merged into one
    multipolygon.  Same construction, draws and order as the program's
    ``mosaic_tpu.bench.workloads.taxi_zones``; ``detail = (split,
    levels)`` then refines every wiggled border (``_detail``, drawing
    after the program's draws), so that a zone has as many vertices as
    a real border of its size.  The default (1, 0) adds none."""
    rng = np.random.default_rng(seed)
    xs = np.linspace(bbox[0], bbox[2], n_side + 1)
    ys = np.linspace(bbox[1], bbox[3], n_side + 1)
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    jx = (xs[1] - xs[0]) * 0.25
    jy = (ys[1] - ys[0]) * 0.25
    nodes = np.stack([gx, gy], axis=-1)
    jitter = rng.uniform(-1, 1, nodes.shape) * np.array([jx, jy])
    jitter[0, :, 0] = jitter[-1, :, 0] = 0.0
    jitter[:, 0, 1] = jitter[:, -1, 1] = 0.0
    nodes = nodes + jitter
    amp0 = 0.22
    level: Dict[tuple, int] = {}

    def edge_poly(kind, i, j):
        if kind == "h":
            a, b = nodes[i, j], nodes[i + 1, j]
            straight = j == 0 or j == n_side
        else:
            a, b = nodes[i, j], nodes[i, j + 1]
            straight = i == 0 or i == n_side
        if straight:
            return np.array([a, b])
        k = level.get((kind, i, j), 0)
        erng = np.random.default_rng(
            np.random.SeedSequence([seed, 1 + (kind == "v"), i, j, k]))
        coarse = _wiggle(a, b, erng, amp=amp0 * 0.5 ** k)
        return _detail(coarse, erng, detail[0], detail[1], amp0 * 0.5 ** k)

    def build_edges():
        h = [[edge_poly("h", i, j) for j in range(n_side + 1)]
             for i in range(n_side)]
        v = [[edge_poly("v", i, j) for j in range(n_side)]
             for i in range(n_side + 1)]
        return h, v

    def cell_ring(i, j):
        bottom = hedge[i][j]
        right = vedge[i + 1][j]
        top = hedge[i][j + 1][::-1]
        left = vedge[i][j][::-1]
        return np.concatenate([bottom[:-1], right[:-1], top[:-1], left])

    def ring_edges(r):
        return np.stack([r, np.roll(r, -1, axis=0)], axis=1)

    def ring_self_crosses(r):
        return bool(np.any(np.triu(proper_crossings(ring_edges(r),
                                                    ring_edges(r)), 2)))

    def near_box(e, r):
        """Edges of ``e`` whose boxes meet ring ``r``'s box: the only
        ones that can cross it."""
        lo, hi = r.min(axis=0), r.max(axis=0)
        return e[np.all((e.max(axis=1) >= lo) & (e.min(axis=1) <= hi),
                        axis=1)]

    def rings_cross(r1, r2):
        e1, e2 = near_box(ring_edges(r1), r2), near_box(ring_edges(r2), r1)
        return bool(len(e1) and len(e2) and
                    np.any(proper_crossings(e1, e2)))

    near = [(di, dj) for di in range(0, 3) for dj in range(-2, 3)
            if (di, dj) > (0, 0)]
    for _ in range(8):
        hedge, vedge = build_edges()
        rings = {(i, j): cell_ring(i, j) for i in range(n_side)
                 for j in range(n_side)}
        offenders = set()
        for (i, j), r in rings.items():
            if ring_self_crosses(r):
                offenders.add((i, j))
        for i in range(n_side):
            for j in range(n_side):
                for di, dj in near:
                    ni, nj = i + di, j + dj
                    if not (0 <= ni < n_side and 0 <= nj < n_side):
                        continue
                    if rings_cross(rings[(i, j)], rings[(ni, nj)]):
                        offenders.add((i, j))
                        offenders.add((ni, nj))
        if not offenders:
            break
        for i, j in offenders:
            for key in [("h", i, j), ("h", i, j + 1), ("v", i, j),
                        ("v", i + 1, j)]:
                level[key] = level.get(key, 0) + 1
    else:
        raise RuntimeError("taxi_zones did not converge to a partition")

    cells = {}
    for i in range(n_side):
        for j in range(n_side):
            ring = rings[(i, j)]
            k = i * n_side + j
            holes, islands = [], []
            if hole_every and k % hole_every == 3:
                sq = _fit_hole(ring, nodes[i:i + 2, j:j + 2].reshape(4, 2),
                               xs[1] - xs[0], ys[1] - ys[0])
                if sq is not None:
                    holes.append(sq[::-1])      # CW hole
                    islands.append(sq)          # CCW island zone
            cells[(i, j)] = (np.vstack([ring, ring[:1]]), holes, islands)

    zones: List[Zone] = []
    merged = set()
    pending_islands = []
    for n, key in enumerate(sorted(cells)):
        if key in merged:
            continue
        ring, holes, islands = cells[key]
        parts = [[ring, *holes]]
        if merge_every and n % merge_every == 5:
            mate = (n_side - 1 - key[0], n_side - 1 - key[1])
            if mate != key and mate not in merged and mate > key:
                r2, h2, is2 = cells[mate]
                parts.append([r2, *h2])
                pending_islands.extend(is2)
                merged.add(mate)
        pending_islands.extend(islands)
        zones.append(parts)
    zones.extend([[isl]] for isl in pending_islands)
    return zones


GENERATORS = {"taxi_zones": taxi_zones}


def _pack(zones: List[Zone]) -> Dict[str, np.ndarray]:
    coords, ring_len, part_rings, zone_parts = [], [], [], []
    for zone in zones:
        zone_parts.append(len(zone))
        for part in zone:
            part_rings.append(len(part))
            for ring in part:
                ring_len.append(len(ring))
                coords.append(np.asarray(ring, np.float64))
    return {"coords": np.concatenate(coords), "ring_len": np.asarray(ring_len),
            "part_rings": np.asarray(part_rings),
            "zone_parts": np.asarray(zone_parts)}


def _unpack(d) -> List[Zone]:
    coords = d["coords"]
    rings = np.split(coords, np.cumsum(d["ring_len"])[:-1])
    ri = iter(rings)
    parts = [[next(ri) for _ in range(k)] for k in d["part_rings"]]
    pi = iter(parts)
    return [[next(pi) for _ in range(k)] for k in d["zone_parts"]]


def load(spec: dict, cache_dir: str) -> List[Zone]:
    """The zone set ``spec`` (``{"generator": ..., "args": {...}}``)
    names, from ``cache_dir`` when an earlier run made it there."""
    key = json.dumps(spec, sort_keys=True).encode()
    path = os.path.join(cache_dir,
                        hashlib.sha256(key).hexdigest()[:16] + ".npz")
    if os.path.exists(path):
        with np.load(path) as d:
            return _unpack(d)
    zones = GENERATORS[spec["generator"]](**spec["args"])
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, **_pack(zones))
    os.replace(tmp, path)
    return zones


def bbox(zones: List[Zone]) -> np.ndarray:
    """[x0, y0, x1, y1] over every ring of the zone set."""
    pts = np.concatenate([r for z in zones for p in z for r in p])
    return np.concatenate([pts.min(axis=0), pts.max(axis=0)])
