"""Bring-up smoke: the NYC taxi-zone PIP join, end to end, on the TPU.

Drives the main path once, in this one process, through the entry points
a user calls, at the size users run (BASELINE config 1):

* 281 concave multipolygon taxi-zone stand-ins with holes over the NYC
  bbox (``build_workload(n_side=16, grid_name="H3", zones="taxi")``),
  tessellated and indexed at H3 res 9;
* 4 batches of 2^22 points through ``make_streamed_pip_join`` in
  2^18-row chunks, with its f64 host recheck.  The first 50,000 points
  of each batch must equal ``pip_host_truth``; the oracle must leave
  every point the join left unmatched unmatched; the zone histogram
  must sum to the matches;
* the Pallas lattice projection on one 2^18-row chunk, held to the df
  margin contract of tests_tpu.

``--chips 4`` runs only the cross-chip paths on a 4-device mesh: the
sharded streamed join (against the single-chip join and the oracle)
and the overlay that shards both sides over ``all_to_all`` (against
``overlay_host_truth``).

Diagnostics go to stdout; the last line is one JSON object
``{"ok": true, "device": {...}}``.  The script exits nonzero, and prints
no such line, when JAX finds no TPU, when the package is not next to
it, or when any check fails.

    python chip_smoke.py [--seed N] [--chips 4]
"""

import argparse
import contextlib
import json
import sys
import time

import numpy as np

CHUNK = 1 << 18
BATCH = 1 << 22
N_BATCHES = 4
SAMPLE = 50_000
N_FOOTPRINTS = 400


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


class Phases(dict):
    """Wall seconds per named phase; ``with phases("x"):`` times one and
    prints it as it closes (repeated phases add up)."""

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        self[name] = self.get(name, 0.0) + dt
        log(f"phase {name}: {dt:.3f} s")


def batch_points(seed: int, b: int) -> np.ndarray:
    from mosaic_tpu.bench.workloads import nyc_points
    return nyc_points(BATCH, seed=seed * 1000 + b)


def flagship_index(phase, cold: bool):
    """The flagship zones, tessellated and indexed.  ``cold`` times a
    first tessellation (its compiles, or their persistent-cache loads)
    apart from the steady one."""
    from mosaic_tpu.bench.workloads import build_workload
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.parallel.pip_join import DensePIPIndex, build_pip_index
    polys, grid, res = build_workload(n_side=16, grid_name="H3",
                                      zones="taxi")
    check(len(polys) == 281, f"expected 281 zones, got {len(polys)}")
    if cold:
        with phase("tessellate_cold"):
            tessellate(polys, res, grid, keep_core_geom=False)
    with phase("tessellate"):
        chips = tessellate(polys, res, grid, keep_core_geom=False)
    with phase("index"):
        idx = build_pip_index(polys, res, grid, chips=chips)
    log(f"zones {len(polys)} -> {len(chips)} chips at H3 res {res}; "
        f"index {type(idx).__name__}")
    check(isinstance(idx, DensePIPIndex), "flagship index is not dense")
    return polys, grid, res, idx


def check_zones(zone: np.ndarray, pts: np.ndarray, polys, what: str):
    """The first SAMPLE points equal the oracle, and the oracle finds
    no zone for any point the join left unmatched."""
    from mosaic_tpu.parallel.pip_join import pip_host_truth
    bad = int(np.sum(zone[:SAMPLE] != pip_host_truth(pts[:SAMPLE], polys)))
    unmatched = np.nonzero(zone < 0)[0]
    dropped = int(np.sum(pip_host_truth(pts[unmatched], polys) >= 0))
    log(f"{what}: {bad}/{SAMPLE} sample mismatches vs pip_host_truth; "
        f"{dropped} of {len(unmatched)} unmatched points inside a zone")
    check(bad == 0, f"{what}: {bad} sample mismatches")
    check(dropped == 0, f"{what}: {dropped} dropped points")


def one_chip(seed: int, phase) -> dict:
    import jax
    import jax.numpy as jnp
    from mosaic_tpu.core.index.h3.jaxkernel import pick_precision
    from mosaic_tpu.obs import metrics
    from mosaic_tpu.parallel.pip_join import (make_streamed_pip_join,
                                              zone_histogram)
    check(pick_precision("auto") == "df",
          f"precision auto -> {pick_precision('auto')}, expected df")
    polys, grid, _, idx = flagship_index(phase, cold=True)
    sjoin = make_streamed_pip_join(idx, grid, polys=polys, chunk=CHUNK)
    with phase("compile"):
        sjoin(batch_points(seed, 0)[:CHUNK])
    hist_fn = jax.jit(lambda z: zone_histogram(z, len(polys)))
    rechecked = 0
    recheck0 = metrics.counter_value("pip_join/recheck_s")
    for b in range(N_BATCHES):
        pts = batch_points(seed, b)
        with phase("join"):
            zone, n_rechecked = sjoin(pts)
        rechecked += int(n_rechecked)
        matched = int(np.sum(zone >= 0))
        hist = int(np.asarray(hist_fn(jnp.asarray(zone))).sum())
        log(f"batch {b}: {BATCH} pts, matched {matched}, rechecked "
            f"{int(n_rechecked)}, histogram sum {hist}")
        check(hist == matched, f"batch {b}: histogram sums to {hist}, "
              f"{matched} points matched")
        check_zones(zone, pts, polys, f"batch {b}")
    phase["recheck_overlapped"] = (
        metrics.counter_value("pip_join/recheck_s") - recheck0)
    log(f"host recheck (overlapped inside join): "
        f"{phase['recheck_overlapped']:.3f} s")
    pallas_phase(phase, seed)
    return {"uncertain_frac": rechecked / (N_BATCHES * BATCH)}


def pallas_phase(phase, seed: int) -> None:
    """The Pallas projection on one chunk vs the f64 host lattice,
    under the df margin contract of tests_tpu."""
    import jax.numpy as jnp
    from mosaic_tpu.core.index.h3 import hexmath as hm
    from mosaic_tpu.core.index.h3.jaxkernel import err_lattice_bound
    from mosaic_tpu.ops.pallas_projection import project_lattice_pallas
    origin = (-74.0, 40.7)
    res = 9
    loc = (batch_points(seed, 0)[:CHUNK] - np.asarray(origin)[None]) \
        .astype(np.float32)
    with phase("pallas"):
        fd, ad, bd, margin, _ = [np.asarray(v) for v in
                                 project_lattice_pallas(
                                     jnp.asarray(loc), res, origin)]
    latlng = np.radians((loc.astype(np.float64) +
                         np.asarray(origin)[None])[:, ::-1])
    fh, hex2d = hm.project_lattice(latlng, res)
    ijk = hm.hex2d_to_ijk(hex2d)
    ah, bh = ijk[:, 0] - ijk[:, 2], ijk[:, 1] - ijk[:, 2]
    dis = ~((fd == fh) & (ad == ah) & (bd == bh))
    unflagged = int(np.sum(dis & (margin >= err_lattice_bound(
        res, "df", 0.4))))
    log(f"pallas projection: {CHUNK} rows, {int(dis.sum())} cells differ "
        f"from the f64 host, {unflagged} outside the df margin")
    check(unflagged == 0, f"pallas: {unflagged} unflagged disagreements")


def four_chips(seed: int, phase) -> dict:
    """Only what exists across chips: the sharded join and overlay."""
    import jax
    from jax.sharding import Mesh
    from mosaic_tpu.core.geometry.array import GeometryBuilder
    from mosaic_tpu.obs import metrics
    from mosaic_tpu.parallel.overlay import (overlay_host_truth,
                                             overlay_intersects)
    from mosaic_tpu.parallel.pip_join import (
        make_sharded_streamed_pip_join, make_streamed_pip_join)
    polys, grid, res, idx = flagship_index(phase, cold=False)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    single = make_streamed_pip_join(idx, grid, polys=polys, chunk=CHUNK)
    sharded = make_sharded_streamed_pip_join(idx, grid, mesh,
                                             polys=polys, chunk=CHUNK)
    with phase("compile"):
        single(batch_points(seed, 0)[:CHUNK])
        sharded(batch_points(seed, 0)[:CHUNK])
    keys = [f"{d.platform}:{d.id}" for d in mesh.devices.flat]
    staged0 = {k: metrics.counter_value(f"shard/staged_rows/pip_join/{k}")
               for k in keys}
    for b in range(N_BATCHES):
        pts = batch_points(seed, b)
        with phase("join"):
            z1, _ = single(pts)
        with phase("sharded_join"):
            z4, _ = sharded(pts)
        bad = int(np.sum(z1 != z4))
        log(f"batch {b}: sharded vs single-chip {bad}/{BATCH} mismatches")
        check(bad == 0, f"batch {b}: sharded join differs from single")
        check_zones(z4, pts, polys, f"batch {b} sharded")
    staged = {k: int(metrics.counter_value(
        f"shard/staged_rows/pip_join/{k}") - staged0[k]) for k in keys}
    log(f"sharded rows staged per device: {staged}")
    check(min(staged.values()) > 0,
          f"points did not land on all four chips: {staged}")

    rng = np.random.default_rng(seed)
    fb = GeometryBuilder()
    for _ in range(N_FOOTPRINTS):
        cx, cy = rng.uniform(-74.2, -73.75), rng.uniform(40.55, 40.85)
        w, h = rng.uniform(2e-4, 2e-3, 2)
        fb.add_polygon(np.array([[cx - w, cy - h], [cx + w, cy - h],
                                 [cx + w, cy + h], [cx - w, cy + h],
                                 [cx - w, cy - h]]))
    foot = fb.finish()
    with phase("overlay_sharded"):
        ov = overlay_intersects(foot, polys, res, grid, mesh=mesh)
    truth = overlay_host_truth(foot, polys)
    bad = int(np.sum(ov != truth))
    log(f"sharded overlay {len(foot)} footprints x {len(polys)} zones: "
        f"{int(truth.sum())} intersecting pairs, {bad} mismatches vs "
        f"overlay_host_truth")
    check(bad == 0, "sharded overlay differs from the host oracle")
    return {"staged_rows": staged}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated input")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths on a 2x2 mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX sees "
                 f"{devices[0].platform!r}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    from mosaic_tpu import native
    from mosaic_tpu.obs import install_jax_listeners, metrics
    from mosaic_tpu.perf.jit_cache import persistent_cache_dir
    metrics.enable()
    install_jax_listeners()
    log(f"device {devices[0].device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {persistent_cache_dir()}; "
        f"native geometry library "
        f"{'loaded' if native.get_lib() is not None else 'absent'}")

    phase = Phases()
    extra = (four_chips if args.chips == 4 else one_chip)(args.seed, phase)

    for name in ("pip_join/refine_bailouts", "pip_join/route_host"):
        n = metrics.counter_value(name)
        log(f"{name}: {n:g}")
        check(n == 0, f"{name} = {n:g}")
    log(json.dumps({
        "phases_s": dict(phase),
        "persistent_cache": {
            "hits": int(metrics.counter_value("jax/cache/cache_hits")),
            "misses": int(metrics.counter_value("jax/cache/cache_misses"))},
        "peak_bytes_in_use": [d.memory_stats().get("peak_bytes_in_use", 0)
                              for d in devices[:args.chips]],
        **extra}))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
