"""mosaic_tpu — TPU-native geospatial analytics framework.

A from-scratch JAX/XLA/Pallas re-design of the capability surface of
Databricks Mosaic (reference: /root/reference, databrickslabs/mosaic
v0.4.3): vector geometry ops (st_*), hierarchical grid indexing (H3 / BNG /
custom rectangular), polygon chipping for index-accelerated spatial joins
(grid_*), raster processing (rst_*), and a SpatialKNN transformer — with
columnar geometry batches in device HBM and distribution via
jax.sharding/shard_map over TPU meshes instead of Spark executors.

Entry point mirrors the reference (python/mosaic/api/enable.py:15):

    import mosaic_tpu as mos
    ctx = mos.enable_mosaic(index_system="H3")
    cells = ctx.grid_longlatascellid(lons, lats, 9)
"""

import jax as _jax

# Cell ids are int64 bit patterns (H3 reserves the high bits;
# core/index/IndexSystem.scala stores Long ids) — 64-bit integer support is
# a hard requirement, not a preference.  Device float compute stays float32
# throughout (every kernel requests its dtype explicitly), so this does not
# push f64 matmuls onto the MXU.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache for every process that imports the
# package: tessellation/join kernels compile once per (pow2-bucketed)
# shape class, and without a disk cache each new process pays those
# compiles again (measured 7.1 s of an 18 s real-zone tessellation).
# JAX_COMPILATION_CACHE_DIR wins when set; else the cache lives in the
# checkout's .jax_cache/ (perf/jit_cache.py).
from .perf.jit_cache import configure_persistent_cache as _cache

_cache()

from .config import MosaicConfig, default_config, set_default_config
from .core.geometry.array import GeometryArray, GeometryBuilder, GeometryType
from .core.geometry.wkb import read_wkb, write_wkb
from .core.geometry.wkt import read_wkt, write_wkt
from .core.geometry.geojson import read_geojson, write_geojson
from .core.index.factory import get_index_system
from .core.tessellate import tessellate, polyfill, point_chips
from .types import ChipSet
from .sql import SQLSession, prettified
from . import io  # noqa: F401  (mos.io.read_vector / read_gpkg / ...)

__version__ = "0.1.0"


def enable_mosaic(index_system: str = "H3", geometry_api: str = "JAX"):
    """Build the framework context (reference: MosaicContext.build,
    functions/MosaicContext.scala:1110 + enable_mosaic,
    python/mosaic/api/enable.py:15)."""
    from .functions.context import MosaicContext
    return MosaicContext.build(index_system, geometry_api)
