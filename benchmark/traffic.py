"""The one general generator of request points, driven by a mix file.

A traffic mix (``benchmark/traffic/<mix>.json``) is data:

* ``points_per_request``: rows in one request;
* ``distribution``: a list of components ``{"weight": w, "bbox":
  [x0, y0, x1, y1] | "config"}``; each point draws its component by
  weight, then lies uniform in that box ("config" is the box of the
  configuration's zones);
* ``loop``: ``{"kind": "closed", "clients": 1}``, the one loop the
  harness drives;
* ``queue_depth``: finished requests the generator may hold ahead of
  the harness.

A request's points come from ``(seed, request index)`` alone; the
positions whose answers are checked come from the seed too.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

#: the streams drawn from one seed
REQUEST, SAMPLE, WARMUP, CAP = 0, 1, 2, 3


def rng_of(seed: int, index: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), index, stream])


class Mix:
    """A traffic mix bound to one configuration's zone box."""

    def __init__(self, spec: dict, config_bbox):
        self.n = int(spec["points_per_request"])
        comps = spec["distribution"]
        self.weights = np.asarray([c["weight"] for c in comps], np.float64)
        if np.any(self.weights <= 0) or not len(comps):
            raise ValueError("traffic: every component needs weight > 0")
        self.weights /= self.weights.sum()
        self.boxes = np.asarray(
            [config_bbox if c["bbox"] == "config" else c["bbox"]
             for c in comps], np.float64)
        self.depth = int(spec.get("queue_depth", 2))
        loop = spec["loop"]
        if loop["kind"] != "closed" or loop.get("clients", 1) != 1:
            raise ValueError(f"traffic: unsupported loop {loop}")

    def points(self, seed: int, index: int, stream: int = REQUEST,
               n: int = 0, out: Optional[np.ndarray] = None) -> np.ndarray:
        """[n, 2] float64 points of request ``index`` (default n: the
        mix's request size), written into ``out`` where given: the same
        values, without a fresh allocation."""
        n = n or self.n
        rng = rng_of(seed, index, stream)
        lo, span = self.boxes[:, :2], self.boxes[:, 2:] - self.boxes[:, :2]
        if len(self.boxes) > 1:
            comp = np.searchsorted(np.cumsum(self.weights)[:-1],
                                   rng.random(n), side="right")
        u = rng.random((n, 2), out=out)
        for axis in (0, 1):
            col = u[:, axis]
            if len(self.boxes) == 1:
                col *= span[0, axis]
                col += lo[0, axis]
            else:
                col *= span[comp, axis]
                col += lo[comp, axis]
        return u

    def sample(self, seed: int, index: int, n: int,
               fraction: float) -> np.ndarray:
        """Positions, out of ``n``, of request ``index`` whose answers
        are checked: about ``fraction`` of them."""
        k = max(1, int(round(n * fraction)))
        return rng_of(seed, index, SAMPLE).integers(0, n, k)


#: Threads a ``Feeder`` makes requests on.  On the v5e host one thread
#: makes a 2^22-point request in 46-54 ms (122 ms into a fresh array,
#: which then takes 6 ms to free); three make one every 20-22 ms, about
#: a fifth of the program's own 97-ms cycle there (PERF.md section 6).
WORKERS = 3


class Feeder:
    """Makes requests ahead of need on ``WORKERS`` threads; ``get``
    hands them out in index order 0, 1, 2, ..., with how long the
    caller waited for each (the generator running late).

    Each worker makes whole requests with ``Mix.points``, so request
    ``i`` is the same bit for bit whichever thread made it.  At most
    ``depth + WORKERS`` requests are made or being made ahead of the
    harness: ``depth`` waiting, and one in progress a worker.  They are
    written into a fixed set of buffers, one more than that: the
    harness frees no request and no worker faults in fresh pages
    while the window runs."""

    def __init__(self, mix: Mix, seed: int):
        self.mix, self.seed = mix, seed
        self.ahead = mix.depth + WORKERS
        self.cv = threading.Condition()
        self.ready: Dict[int, np.ndarray] = {}
        self.free = [np.empty((mix.n, 2)) for _ in range(self.ahead + 1)]
        self.handed: Optional[np.ndarray] = None
        self.claimed = self.taken = 0
        self.stopped = False
        self.error: Optional[BaseException] = None
        self.waited: List[float] = []
        self.made: List[float] = []
        self.threads = [threading.Thread(target=self._run, daemon=True,
                                         name=f"bench-feeder-{k}")
                        for k in range(WORKERS)]
        for t in self.threads:
            t.start()

    def _run(self) -> None:
        while True:
            with self.cv:
                self.cv.wait_for(lambda: self.stopped or
                                 self.claimed - self.taken < self.ahead)
                if self.stopped:
                    return
                i = self.claimed
                self.claimed += 1
                buf = self.free.pop()
            t0 = time.perf_counter()
            try:
                pts = self.mix.points(self.seed, i, out=buf)
            except BaseException as e:  # handed to ``get``, not lost
                with self.cv:
                    self.error = e
                    self.cv.notify_all()
                return
            with self.cv:
                self.ready[i] = pts
                self.made.append(time.perf_counter() - t0)
                self.cv.notify_all()

    def fill(self, timeout: float = 120.0) -> None:
        """Block until the next ``depth`` requests are made (set-up:
        the window opens with the generator ahead)."""
        with self.cv:
            self.cv.wait_for(lambda: self.error is not None or all(
                self.taken + k in self.ready
                for k in range(self.mix.depth)), timeout)

    def get(self) -> Tuple[int, np.ndarray]:
        """The next request.  Its points stay as they are until the next
        ``get``, which hands their buffer back to the workers."""
        t0 = time.perf_counter()
        with self.cv:
            self.cv.wait_for(lambda: self.taken in self.ready
                             or self.error is not None)
            if self.taken not in self.ready:
                raise RuntimeError("bench feeder failed") from self.error
            if self.handed is not None:
                self.free.append(self.handed)
            i = self.taken
            self.taken += 1
            pts = self.handed = self.ready.pop(i)
            self.cv.notify_all()
        self.waited.append(time.perf_counter() - t0)
        return i, pts

    def close(self) -> None:
        with self.cv:
            self.stopped = True
            self.cv.notify_all()
        for t in self.threads:
            t.join(timeout=30)
        if any(t.is_alive() for t in self.threads):
            raise RuntimeError("bench feeder thread did not stop")
