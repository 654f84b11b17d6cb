"""Reduce a profiler trace to device busy and idle time.

``read_xplane`` takes from one ``.xplane.pb`` the device operations
(the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the
benchmark's own host marks (``bench/window``, ``bench/request``,
``bench/generate``, ``bench/between``), all on the
profiler's one clock.
``reduce`` turns them into:

* ``window_s``: the traced window, from the ``bench/window`` mark;
* ``busy_s``: per device, the length of the union of its operation
  intervals inside the window; and their mean;
* ``device_ops``: the ten operation names with the most device time
  (summed over devices, divided by their number);
* ``idle_gaps``: the ten longest stretches in which a device ran
  nothing, each named by what the benchmark was doing then;
* ``request_host_s``: per request, its wall time less the device's
  busy time inside it (mean over devices).
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MARKS = {"bench/request": "request", "bench/generate": "generate",
         "bench/between": "between requests"}
WINDOW = "bench/window"
#: "%fusion.176 = (f32[..]{..}, ..) fusion(...), ..." -> "%fusion.176 fusion"
HLO = re.compile(r"^(%\S+) = .*? ([a-z][\w-]*)\(")

Intervals = np.ndarray  # [K, 2] float64 ns, sorted, disjoint


def short(name: str, _seen: Dict[str, str] = {}) -> str:
    """An XLA op's instruction name and opcode, without its shapes."""
    if name not in _seen:
        m = HLO.match(name)
        _seen[name] = f"{m.group(1)} {m.group(2)}" if m else name[:80]
    return _seen[name]


def read_xplane(path: str):
    """(ops, marks): ``ops[device] = (starts_ns, ends_ns, names)`` and
    ``marks = [(start_ns, end_ns, name)]`` of the benchmark's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, Tuple[np.ndarray, np.ndarray, List[str]]] = {}
    marks: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            t, names = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        t.append((e.start_ns, e.duration_ns))
                        names.append(short(e.name))
            t = np.asarray(t, np.float64).reshape(-1, 2)
            ops[plane.name] = (t[:, 0], t[:, 0] + t[:, 1], names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks.extend((e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in line.events
                             if e.name.startswith("bench/"))
    return ops, marks


def merge(iv: np.ndarray) -> Intervals:
    """Union of [start, end] intervals as sorted disjoint intervals."""
    iv = np.asarray(iv, np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def covered(merged: Intervals, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Length of ``merged`` inside each [a_i, b_i]."""
    a, b = np.atleast_1d(a).astype(np.float64), np.atleast_1d(b).astype(
        np.float64)
    if not len(merged):
        return np.zeros(len(a))
    s, e = merged[:, 0], merged[:, 1]
    cum = np.r_[0.0, np.cumsum(e - s)]

    def upto(t):
        # covered length of (-inf, t]
        k = np.searchsorted(s, t, side="right")
        part = np.clip(t - s[np.maximum(k - 1, 0)], 0,
                       (e - s)[np.maximum(k - 1, 0)])
        return cum[np.maximum(k - 1, 0)] + np.where(k > 0, part, 0.0)

    return np.maximum(upto(b) - upto(a), 0.0)


def gaps(merged: Intervals, w0: float, w1: float) -> Intervals:
    """Stretches of [w0, w1] that ``merged`` leaves uncovered."""
    inside = merged[(merged[:, 1] > w0) & (merged[:, 0] < w1)] \
        if len(merged) else merged
    edges = np.r_[w0, np.clip(inside.ravel(), w0, w1), w1].reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def reduce(ops, marks, top: int = 10) -> dict:
    """The summary of the module docstring; ``None`` where the trace
    holds no device operation or no window mark.  ``ops[device]`` is
    ``(starts_ns, ends_ns, names)``."""
    win = [(s, e) for s, e, n in marks if n == WINDOW]
    devs = sorted((d for d in ops if len(ops[d][2])),
                  key=lambda d: int(DEVICE_PLANE.match(d).group(2)))
    if not win or not devs:
        return None
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    kinds = sorted(set(MARKS.values()))
    kind_iv = [merge([(s, e) for s, e, n in marks if MARKS.get(n) == k])
               for k in kinds]
    req = np.asarray([(s, e) for s, e, n in marks if n == "bench/request"],
                     np.float64).reshape(-1, 2)
    busy, op_time, idle, req_busy = {}, {}, [], np.zeros(len(req))
    for d in devs:
        starts, ends, names = ops[d]
        cs, ce = np.clip(starts, w0, w1), np.clip(ends, w0, w1)
        keep = ce > cs
        m = merge(np.stack([cs[keep], ce[keep]], axis=1))
        busy[d] = float(covered(m, w0, w1)[0]) * 1e-9
        uniq, code = np.unique(np.asarray(names, object)[keep],
                               return_inverse=True)
        for name, t in zip(uniq, np.bincount(code, weights=(ce - cs)[keep])):
            op_time[name] = op_time.get(name, 0.0) + t
        g = gaps(m, w0, w1)
        g = g[np.argsort(g[:, 0] - g[:, 1], kind="stable")[:top]]
        share = np.stack([covered(iv, g[:, 0], g[:, 1]) for iv in kind_iv])
        for k, (g0, g1) in enumerate(g):
            name = kinds[int(np.argmax(share[:, k]))] \
                if share[:, k].max() > 0 else "other"
            idle.append((name, (g1 - g0) * 1e-9))
        if len(req):
            req_busy += covered(m, req[:, 0], req[:, 1])
    n = len(devs)
    req_host = (req[:, 1] - req[:, 0] - req_busy / n) * 1e-9 \
        if len(req) else np.zeros(0)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "devices": devs,
        "busy_s_by_device": busy,
        "busy_s": sum(busy.values()) / n,
        "device_ops": [[k, float(v) * 1e-9 / n] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, float(v)] for k, v in sorted(
            idle, key=lambda kv: -kv[1])[:top]],
        "request_host_s": req_host.tolist(),
    }
