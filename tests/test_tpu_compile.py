"""Compile the main path's kernels for a TPU v5e that is described, not
attached.

Nothing runs: these tests catch what the TPU compiler refuses (block
shapes off the (8, 128) tiling, 64-bit values inside a Pallas kernel,
programs that do not fit the device) at no chip time.  The topology is
described inside a module fixture, never at import, so every xdist
worker collects the same tests and only the worker running this file
loads the TPU library.

``make_pip_join_fn`` is pinned to ``precision="df"``: here ``"auto"``
sees the CPU and would pick f64, while the chip takes df.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

CHUNK = 1 << 18
#: HBM of one v5e chip
V5E_HBM = 16 * 2**30


def _tiled(a) -> int:
    """Device bytes of a 2-D f32 array in (8, 128) tiles."""
    rows, lanes = a.shape
    return -(-rows // 8) * 8 * -(-lanes // 128) * 128 * 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def flagship(no_persistent_cache):
    """BASELINE config 1: 281 taxi-zone stand-ins indexed at H3 res 9."""
    from mosaic_tpu.bench.workloads import build_workload
    from mosaic_tpu.parallel.pip_join import (DensePIPIndex,
                                              build_pip_index)
    polys, grid, res = build_workload(n_side=16, grid_name="H3",
                                      zones="taxi")
    idx = build_pip_index(polys, res, grid)
    assert isinstance(idx, DensePIPIndex)
    return idx, grid


def test_chunk_join_compiles_for_v5e(topo, flagship):
    from mosaic_tpu.parallel.pip_join import make_pip_join_fn
    idx, grid = flagship
    one = SingleDeviceSharding(topo.devices[0])
    pts = jax.ShapeDtypeStruct((CHUNK, 2), jnp.float32, sharding=one)
    fn = make_pip_join_fn(idx, grid, precision="df")
    compiled = fn.lower(pts).compile()
    mem = compiled.memory_analysis()
    # the record table is an argument of the program, not a constant
    assert mem.argument_size_in_bytes == CHUNK * 2 * 4 + _tiled(idx.rec)
    # the join stays chunked: one chunk's temporaries are a small part
    # of the chip's memory
    assert 0 < mem.temp_size_in_bytes < V5E_HBM // 4
    text = compiled.as_text()
    # one gather a point: the cell's record row, whose code lane is the
    # cell lookup; no int32 entry gather ahead of it
    assert idx.layout == "cell_rows"
    R = idx.rec.shape[-1]
    gathers = re.findall(rf"= \w+\[{CHUNK}[,\]]\S* gather\(", text)
    assert gathers == re.findall(
        rf"= f32\[{CHUNK},{R}\]\S* gather\(", text), gathers
    assert len(gathers) == 1, gathers
    assert not re.search(rf"= s32\[{CHUNK}\]\S* gather\(", text)
    # the record is read as one block: no per-component [N, E, k] array
    assert not re.search(rf"f32\[{CHUNK},{idx.E},\d+\]", text)


def test_pallas_projection_compiles_for_v5e(topo, no_persistent_cache):
    from mosaic_tpu.ops.pallas_projection import project_lattice_pallas
    one = SingleDeviceSharding(topo.devices[0])
    pts = jax.ShapeDtypeStruct((CHUNK, 2), jnp.float32, sharding=one)
    compiled = jax.jit(
        lambda p: project_lattice_pallas(p, 9, (-74.0, 40.7))
    ).lower(pts).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_join_step_compiles_for_4_chips(topo, flagship):
    from mosaic_tpu.parallel.pip_join import make_pip_join_fn
    idx, grid = flagship
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    out = NamedSharding(mesh, P("data"))
    pts = jax.ShapeDtypeStruct((CHUNK, 2), jnp.float32, sharding=rows)
    fn = make_pip_join_fn(idx, grid, precision="df")
    mem = jax.jit(fn, in_shardings=(rows,), out_shardings=(out, out)) \
        .lower(pts).compile().memory_analysis()
    # per-device figures: each chip holds a quarter of the points and
    # the whole record table
    assert mem.argument_size_in_bytes == CHUNK * 2 * 4 // 4 + _tiled(idx.rec)
    assert 0 < mem.temp_size_in_bytes < V5E_HBM // 4
