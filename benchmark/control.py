"""The control of ``correct``: the reference, one precision lower, put
in the program's place.

``float32_in_place(cell, seed)`` is a ``wrap`` for ``harness.run_cell``
or ``harness.measure``: the timed path becomes the float32 reference,
which answers every position that the check of request ``i`` reads
(drawn from ``(seed, i)`` as the harness draws them); the positions no
check reads hold -2.  A run so wrapped has to come out ``correct:
false``.  The benchmark's own runs never use it; ``proof.py`` and the
tests do.
"""

from __future__ import annotations

import itertools
from typing import Optional

import numpy as np

import harness
import reference
import traffic
import zones as zonesets


def float32_in_place(cell: str, seed: int, overrides: Optional[dict] = None):
    _, _, config, spec = harness.load_cell(cell)
    rings = zonesets.load(config["zones"], harness.ZONE_CACHE)
    mix = traffic.Mix({**spec, **(overrides or {})}, zonesets.bbox(rings))
    ref32 = reference.Reference(rings, np.float32)

    def wrap(_run):
        index = itertools.count()

        def control(pts):
            pos = mix.sample(seed, next(index), len(pts),
                             config["check_fraction"])
            zone = np.full(len(pts), -2, np.int32)
            zone[pos] = ref32.zones_of(pts[pos])
            return zone, 0
        return control
    return wrap
