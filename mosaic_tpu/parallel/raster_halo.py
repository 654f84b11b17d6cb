"""Sharded-raster halo exchange: stencils over a row-sharded raster.

Reference counterpart: the GDALBlock + Padding machinery
(core/raster/gdal/GDALBlock.scala) that the reference uses to run
stencil operators over tiled rasters — each block reads a halo of
neighbouring pixels so window operators are exact at block seams.

TPU-native redesign: the raster shards as row slabs over the mesh's
data axis and the halo is TWO ``jax.lax.ppermute`` shifts inside a
``shard_map`` — each device sends its top rows up and bottom rows down
the ring, concatenates [halo_above; slab; halo_below], and runs the
stencil on the widened slab.  The collectives ride ICI; no host
round-trips, no re-tiling.  Outer edges replicate the zero padding of
the single-device operator, so the sharded result equals
``rops.convolve`` to f32 reduction-order tolerance (pinned by
tests/test_raster_halo.py).
"""

from __future__ import annotations

import numpy as np

from ..core.raster.tile import RasterTile
from ..perf.jit_cache import kernel_cache
from ..perf.pipeline import stream

__all__ = ["sharded_convolve", "sharded_convolve_stream"]


def _convolve_fn(kernel: np.ndarray, mesh, axis: str, shape):
    """Validate + return the compiled sharded stencil for tiles of
    ``shape`` = (bands, H, W) (cached in the process kernel cache)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    k = np.asarray(kernel, np.float64)
    kh, kw = k.shape
    if kh % 2 == 0 or kw % 2 == 0:
        raise ValueError("sharded_convolve requires odd kernel dims "
                         "(same-shape output)")
    halo = kh // 2
    D = mesh.shape[axis]
    bands, H, W = shape
    if H % D != 0:
        raise ValueError(f"the {axis} axis size {D} must divide the "
                         f"tile height {H} (retile or pad first)")
    if H // D < halo:
        raise ValueError(f"slab height {H // D} smaller than the "
                         f"kernel halo {halo}")
    kj = jnp.asarray(k.astype(np.float32))

    def local(slab):
        # slab [bands, H/D, W]; exchange halo rows around the ring
        idx = jax.lax.axis_index(axis)
        up = [(i, (i - 1) % D) for i in range(D)]      # send towards 0
        down = [(i, (i + 1) % D) for i in range(D)]
        # rows just above my slab = PREVIOUS device's bottom rows
        # (sent downward); rows below = NEXT device's top rows
        above_rx = jax.lax.ppermute(slab[:, -halo:], axis, down)
        below_rx = jax.lax.ppermute(slab[:, :halo], axis, up)
        # outer edges: zero rows, matching the SAME-pad zero fill of
        # the single-device convolve
        above = jnp.where(idx == 0, jnp.zeros_like(above_rx),
                          above_rx)
        below = jnp.where(idx == D - 1, jnp.zeros_like(below_rx),
                          below_rx)
        wide = jnp.concatenate([above, slab, below], axis=1)
        out = jax.lax.conv_general_dilated(
            wide[:, None], kj[None, None], window_strides=(1, 1),
            padding=((0, 0), (kw // 2, kw // 2)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return out[:, 0]

    # cache the compiled stencil: a fresh closure per call would
    # retrace + recompile for every same-shaped tile in a pipeline
    key = (id(mesh), axis, D, kh, kw, bands, H, W, k.tobytes())
    return kernel_cache.get_or_build(
        "raster/halo_convolve", key,
        lambda: jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=P(None, axis, None),
            out_specs=P(None, axis, None))))


def _count_halo_bytes(kernel, mesh, axis, shape, n_tiles=1):
    from ..obs import metrics
    if metrics.enabled:
        # two ppermute shifts move `halo` rows per device each way:
        # bands * halo * W f32 per device per shift, D devices
        halo = np.asarray(kernel).shape[0] // 2
        D = mesh.shape[axis]
        bands, _, W = shape
        moved = 2.0 * D * bands * halo * W * 4 * n_tiles
        metrics.count("collective/ppermute_bytes", moved)
        metrics.count("collective/ppermute_bytes/raster_halo", moved)
        metrics.count("collective/ppermute_calls", 2 * n_tiles)


def sharded_convolve(tile: RasterTile, kernel: np.ndarray, mesh,
                     axis: str = "data") -> RasterTile:
    """rops.convolve over a mesh: row-slab sharding + halo exchange.

    The mesh axis size must divide the tile's height (callers can
    retile/pad; keeping the constraint explicit avoids silently uneven
    slabs)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    fn = _convolve_fn(kernel, mesh, axis, tile.data.shape)
    data = np.where(tile.valid_mask(),
                    np.asarray(tile.data, np.float32), 0.0)
    from ..obs import tracer
    from ..obs.context import root_trace
    _count_halo_bytes(kernel, mesh, axis, tile.data.shape)
    arr = jax.device_put(
        jnp.asarray(data),
        NamedSharding(mesh, P(None, axis, None)))
    with root_trace("raster_halo"), tracer.span("halo/convolve"):
        out = np.asarray(fn(arr))
    return RasterTile(out, tile.gt, nodata=None, srid=tile.srid,
                      meta={"op": "convolve", "sharded": "halo"})


def sharded_convolve_stream(tiles, kernel: np.ndarray, mesh,
                            axis: str = "data") -> list:
    """Convolve MANY same-shaped tiles with upload/compute overlap.

    One compiled stencil serves the whole batch; the double-buffered
    executor uploads tile N+1 while the collectives run on tile N and
    fetches tile N-1 on a worker thread (perf.pipeline.stream).
    Returns the output :class:`RasterTile` list in input order."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    tiles = list(tiles)
    if not tiles:
        return []
    shape = tiles[0].data.shape
    for t in tiles[1:]:
        if t.data.shape != shape:
            raise ValueError(
                f"sharded_convolve_stream needs same-shaped tiles "
                f"(got {t.data.shape} after {shape}); group by shape "
                "first")
    fn = _convolve_fn(kernel, mesh, axis, shape)
    _count_halo_bytes(kernel, mesh, axis, shape, n_tiles=len(tiles))
    sharding = NamedSharding(mesh, P(None, axis, None))

    def put(tile):
        data = np.where(tile.valid_mask(),
                        np.asarray(tile.data, np.float32), 0.0)
        return jax.device_put(jnp.asarray(data), sharding)

    def consume(i, tile, host):
        return RasterTile(host, tile.gt, nodata=None, srid=tile.srid,
                          meta={"op": "convolve", "sharded": "halo"})

    from ..obs import tracer
    from ..obs.context import root_trace
    with root_trace("raster_halo"), tracer.span("halo/convolve_stream"):
        return stream(tiles, compute=fn, put=put, consume=consume)
