"""The one general generator of request points, driven by a mix file.

A traffic mix (``benchmark/traffic/<mix>.json``) is data:

* ``points_per_request``: rows in one request;
* ``distribution``: a list of components ``{"weight": w, "bbox":
  [x0, y0, x1, y1] | "config"}``; each point draws its component by
  weight, then lies uniform in that box ("config" is the box of the
  configuration's zones);
* ``loop``: ``{"kind": "closed", "clients": 1}``, the one loop the
  harness drives;
* ``queue_depth``: requests the generator thread may run ahead.

A request's points come from ``(seed, request index)`` alone; the
positions whose answers are checked come from the seed too.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import List, Tuple

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
               n: int = 0) -> np.ndarray:
        """[n, 2] float64 points of request ``index`` (default n: the
        mix's request size)."""
        n = n or self.n
        rng = rng_of(seed, index, stream)
        lo, span = self.boxes[:, :2], self.boxes[:, 2:] - self.boxes[:, :2]
        if len(self.boxes) > 1:
            comp = np.searchsorted(np.cumsum(self.weights)[:-1],
                                   rng.random(n), side="right")
        u = rng.random((n, 2))
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


class Feeder:
    """Makes requests ahead of need on one thread, through a bounded
    queue; ``get`` hands out the next one and how long the caller
    waited for it (the generator running late)."""

    def __init__(self, mix: Mix, seed: int):
        self.mix, self.seed = mix, seed
        self.q: "queue.Queue" = queue.Queue(maxsize=mix.depth)
        self.stop = threading.Event()
        self.waited: List[float] = []
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-feeder")
        self.thread.start()

    def _run(self) -> None:
        i = 0
        while not self.stop.is_set():
            item = (i, self.mix.points(self.seed, i))
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            i += 1

    def fill(self, timeout: float = 120.0) -> None:
        """Block until the queue holds ``depth`` requests (set-up: the
        window opens with the generator ahead)."""
        t0 = time.perf_counter()
        while not self.q.full() and time.perf_counter() - t0 < timeout:
            time.sleep(0.005)

    def get(self) -> Tuple[int, np.ndarray]:
        t0 = time.perf_counter()
        item = self.q.get()
        self.waited.append(time.perf_counter() - t0)
        return item

    def close(self) -> None:
        self.stop.set()
        self.thread.join(timeout=30)
        if self.thread.is_alive():
            raise RuntimeError("bench feeder thread did not stop")
