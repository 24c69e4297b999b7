"""Ahead-of-time compiles of the served forest paths for a TPU v5e, at the
paper's deployment widths (512 trees; Tables 4/5), against a described
``v5e:2x2`` topology: no chip is needed, and whatever the chip's compiler
would refuse fails here. The topology is described inside a module fixture
(never at import), so every pytest worker collects the same tests and only
the one that runs this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.forest_jax import _predict_dense_jax, _predict_flat_jax
from repro.kernels.forest.kernel import (forest_predict_kernel, leaf_rows,
                                         level_offsets)

TREES = 512
FEATURES = 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch", [1, 256])
def test_pallas_forest_kernel_compiles_for_v5e(one_chip, batch):
    depth = 10
    rows = level_offsets(depth)[-1]
    compiled = forest_predict_kernel.lower(
        _spec((batch, FEATURES), jnp.float32, one_chip),
        _spec((rows, TREES), jnp.int32, one_chip),
        _spec((rows, TREES), jnp.float32, one_chip),
        _spec((leaf_rows(depth), TREES), jnp.float32, one_chip),
        depth=depth, n_trees=TREES, block_b=64, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flat_jax_compiles_for_v5e_at_depth_40(one_chip):
    nodes = TREES * 2000                 # unbounded-depth trees, ~1k leaves
    i32 = _spec((nodes,), jnp.int32, one_chip)
    f32 = _spec((nodes,), jnp.float32, one_chip)
    compiled = _predict_flat_jax.lower(
        i32, f32, i32, i32, f32, _spec((TREES,), jnp.int32, one_chip),
        _spec((256, FEATURES), jnp.float32, one_chip),
        max_depth=40).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("batch", [1, 4096])
def test_flat_jax_level_walk_compiles_for_v5e(one_chip, batch):
    """The TPU's body of the same program, over the deep forest's tables:
    36 levels of 104 slots. The select over the slots is fused: no
    (slots, batch, trees) array is written to the device's memory."""
    levels, slots = 36, 104
    i32 = _spec((levels, slots, TREES), jnp.int32, one_chip)
    f32 = _spec((levels, slots, TREES), jnp.float32, one_chip)
    compiled = _predict_flat_jax.lower(
        i32, f32, i32, f32, _spec((batch, FEATURES), jnp.float32, one_chip),
        max_depth=levels - 1, walk="levels").compile()
    assert compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * slots * batch * TREES


def test_dense_jax_compiles_for_v5e_at_depth_10(one_chip):
    nodes = 2 ** 11 - 1
    compiled = _predict_dense_jax.lower(
        _spec((TREES, nodes), jnp.int32, one_chip),
        _spec((TREES, nodes), jnp.float32, one_chip),
        _spec((TREES, nodes), jnp.float32, one_chip),
        _spec((256, FEATURES), jnp.float32, one_chip), depth=10).compile()
    assert compiled.as_text()
