"""Feature-extraction (StableHLO walker) tests — the CUDA Flux analogue."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import (FEATURE_NAMES, LaunchConfig, extract,
                                 extract_from_text)


def test_matmul_flops_exact():
    m, k, n = 32, 48, 64
    fv = extract(lambda a, b: a @ b,
                 jax.ShapeDtypeStruct((m, k), jnp.float32),
                 jax.ShapeDtypeStruct((k, n), jnp.float32))
    assert fv.aux["flops"] == pytest.approx(2 * m * k * n, rel=0.01)


def test_scan_trip_count_weighting():
    L = 9

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        c, _ = jax.lax.scan(body, x, None, length=L)
        return c

    fv = extract(f, jax.ShapeDtypeStruct((8, 16), jnp.float32),
                 jax.ShapeDtypeStruct((16, 16), jnp.float32))
    assert fv.aux["flops"] == pytest.approx(L * (2 * 8 * 16 * 16) + L * 8 * 16,
                                            rel=0.05)
    assert fv["special_ops"] == pytest.approx(L * 8 * 16, rel=0.01)


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 * 2.0 + 1.0, ()
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, ()
        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c

    fv = extract(f, jax.ShapeDtypeStruct((16,), jnp.float32))
    assert fv["arith_ops"] == pytest.approx(4 * 3 * 16 * 2, rel=0.15)


def test_special_vs_logic_grouping():
    def f(x):
        return jnp.where(x > 0, jnp.exp(x), jnp.sin(x))

    fv = extract(f, jax.ShapeDtypeStruct((100,), jnp.float32))
    assert fv["special_ops"] == pytest.approx(200, rel=0.01)   # exp + sin
    assert fv["logic_ops"] >= 200                              # compare+select


def test_launch_config_features():
    fv = extract(lambda x: x + 1.0, jax.ShapeDtypeStruct((64,), jnp.float32),
                 launch=LaunchConfig(work_items=4096, n_shards=16,
                                     shared_mem_bytes=1024))
    assert fv["work_per_shard"] == 256.0
    assert fv["num_shards"] == 16.0
    assert fv["shared_mem_vol"] == 1024.0


def test_memory_volumes_cover_io():
    n = 128
    fv = extract(lambda a, b: a + b,
                 jax.ShapeDtypeStruct((n, n), jnp.float32),
                 jax.ShapeDtypeStruct((n, n), jnp.float32))
    io = 3 * n * n * 4
    assert fv["global_mem_vol"] >= io


def test_vector_matches_names():
    fv = extract(lambda x: x * 2, jax.ShapeDtypeStruct((8,), jnp.float32))
    assert fv.values.shape == (len(FEATURE_NAMES),)
    d = fv.as_dict()
    assert set(d) == set(FEATURE_NAMES)
    assert all(np.isfinite(v) for v in d.values())


def test_collectives_counted_as_sync():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("d",))

    def f(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                             in_specs=P("d"), out_specs=P())(x)

    fv = extract(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    assert fv["sync_ops"] >= 1


def test_robust_to_unknown_text():
    fv = extract_from_text("garbage that is not mlir", LaunchConfig())
    assert np.isfinite(fv.values).all()
