"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: Mosaic refuses what interpret mode accepts (block shapes not
aligned to the (8, 128) tiling, too much VMEM), so these compiles guard the
chip path from the CPU."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gsproject import gsproject
from repro.kernels.tile_raster import tile_raster

TILES = 1024           # 512x512 px in 16x16 tiles
TILE = 16
K_SWEEP = [256, 1024]  # GSConfig.k_per_tile default, and the largest used
N_GAUSS = 574_464      # miranda at volume_res 512, padded to 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # analysis: allow(hygiene.broad_except, no TPU compiler here: the fixture skips)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _composite():
    return tile_raster.make_composite(512 // TILE, TILE, TILE, 0, interpret=False)


@pytest.mark.parametrize("k", K_SWEEP)
def test_tile_raster_forward_compiles(one_chip, no_persistent_cache, k):
    comp = _composite()
    args = (_spec((TILES, 11, k), one_chip), _spec((TILES, k), one_chip))
    compiled = jax.jit(comp).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", K_SWEEP)
def test_tile_raster_backward_compiles(one_chip, no_persistent_cache, k):
    comp = _composite()

    def loss(splats_t, valid):
        out, tfin = comp(splats_t, valid)
        return jnp.sum(out * out) + jnp.sum(tfin * tfin)

    args = (_spec((TILES, 11, k), one_chip), _spec((TILES, k), one_chip))
    compiled = jax.jit(jax.value_and_grad(loss)).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2  # forward + backward


def test_gsproject_compiles(one_chip, no_persistent_cache):
    run = gsproject.make_project(N_GAUSS, interpret=False)
    args = (
        _spec((3, N_GAUSS), one_chip), _spec((3, N_GAUSS), one_chip),
        _spec((4, N_GAUSS), one_chip), _spec((1, N_GAUSS), one_chip),
        _spec((3, N_GAUSS), one_chip), _spec((1, gsproject.CAM_SLOTS), one_chip),
    )
    compiled = jax.jit(run).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
