"""The plain reference: which zone holds each point.

A straightforward even-odd (crossing number) point-in-polygon test over
every ring of every zone, with a bounding-box prefilter, first match in
zone order.  It imports nothing of the program and takes nothing the
program made: it reads the zone rings the benchmark generated.

``dtype`` float64 is the reference; float32 is the control of
``PERF.md`` (the same test one precision lower), which has to come out
as not correct.
"""

from __future__ import annotations

from typing import List

import numpy as np


class Reference:
    """Zones as flat edge arrays, one per part, ready to assign points.

    A part's rings give it an even-odd parity; a zone's is the parity
    of its parts together.  A point outside a part's bounding box lies
    outside each of its rings, so that part adds nothing and is
    skipped."""

    def __init__(self, zones: List, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        self.parts = []         # per zone: [(box, (ax, ay, bx, by))]
        for zone in zones:
            parts = []
            for part in zone:
                rings = [self._open(r) for r in part]
                a = np.concatenate(rings)
                b = np.concatenate([np.roll(r, -1, axis=0) for r in rings])
                ed = np.stack([a[:, 0], a[:, 1], b[:, 0], b[:, 1]]).astype(
                    self.dtype)
                box = np.concatenate([a.min(axis=0), a.max(axis=0)]).astype(
                    self.dtype)
                parts.append((box, ed))
            self.parts.append(parts)

    @staticmethod
    def _open(ring) -> np.ndarray:
        r = np.asarray(ring, np.float64)[:, :2]
        return r[:-1] if np.array_equal(r[0], r[-1]) else r

    def zones_of(self, points: np.ndarray, block: int = 4096) -> np.ndarray:
        """[N] int32 zone of each point (-1: none), first match."""
        pts = np.asarray(points, np.float64)[:, :2].astype(self.dtype)
        n = len(pts)
        out = np.full(n, -1, np.int32)
        parity = np.zeros(n, bool)
        order = np.argsort(pts[:, 0], kind="stable")
        xs = pts[order, 0]
        for z, parts in enumerate(self.parts):
            touched = []
            for (x0, y0, x1, y1), edges in parts:
                cand = order[np.searchsorted(xs, x0, side="left"):
                             np.searchsorted(xs, x1, side="right")]
                py_all = pts[cand, 1]
                cand = cand[(py_all >= y0) & (py_all <= y1) & (out[cand] < 0)]
                if not len(cand):
                    continue
                ax, ay, bx, by = (e[None] for e in edges)
                for s in range(0, len(cand), block):
                    c = cand[s:s + block]
                    px = pts[c, 0][:, None]
                    py = pts[c, 1][:, None]
                    straddle = (ay <= py) != (by <= py)
                    with np.errstate(divide="ignore", invalid="ignore"):
                        t = (py - ay) / np.where(by == ay, self.dtype.type(1),
                                                 by - ay)
                    xi = ax + t * (bx - ax)
                    parity[c] ^= ((straddle & (px < xi)).sum(axis=1) & 1) \
                        .astype(bool)
                touched.append(cand)
            if touched:
                c = np.unique(np.concatenate(touched))
                out[c[parity[c]]] = z
                parity[c] = False
        return out
