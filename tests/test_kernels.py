"""Per-kernel shape/dtype sweeps against the pure-jnp ref oracles
(deliverable c: assert_allclose per Pallas kernel)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import ExtraTreesRegressor
from repro.core.forest_jax import DenseForestJax, FlatForestJax, to_dense
from repro.kernels.attention import attention_ref, flash_attention
from repro.kernels.forest import forest_predict, forest_predict_ref
from repro.kernels.mamba import ssd_ref, ssd_scan


# ------------------------------------------------------------------ forest

@pytest.fixture(scope="module")
def fitted():
    rng = np.random.default_rng(0)
    X = rng.lognormal(1, 1.5, size=(150, 12)).astype(np.float32)
    y = np.log(2 * X[:, 0] + 0.5 * X[:, 3] + 3) + 0.1 * rng.normal(size=150)
    return ExtraTreesRegressor(n_estimators=12, seed=2).fit(X, y)


@pytest.mark.parametrize("depth", [2, 5, 8, 10])
@pytest.mark.parametrize("batch", [1, 7, 32])
def test_forest_kernel_vs_ref(fitted, depth, batch):
    rng = np.random.default_rng(depth * 100 + batch)
    dense = to_dense(fitted, depth=depth)
    X = rng.lognormal(1, 1.5, size=(batch, 12)).astype(np.float32)
    ref = forest_predict_ref(jnp.asarray(X), jnp.asarray(dense.feature),
                             jnp.asarray(dense.threshold),
                             jnp.asarray(dense.value), depth=depth)
    out = forest_predict(X, dense.feature, dense.threshold, dense.value,
                         depth=depth, block_b=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_forest_dense_jax_matches_ref(fitted):
    dense = to_dense(fitted, depth=6)
    rng = np.random.default_rng(1)
    X = rng.lognormal(1, 1.5, size=(16, 12)).astype(np.float32)
    a = DenseForestJax(dense)(X)
    b = forest_predict_ref(jnp.asarray(X), jnp.asarray(dense.feature),
                           jnp.asarray(dense.threshold),
                           jnp.asarray(dense.value), depth=6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_forest_deep_dense_approaches_exact(fitted):
    rng = np.random.default_rng(3)
    X = rng.lognormal(1, 1.5, size=(32, 12)).astype(np.float32)
    exact = fitted.predict(X)
    deep = to_dense(fitted, depth=14)
    out = np.asarray(forest_predict(X, deep.feature, deep.threshold,
                                    deep.value, depth=14))
    assert np.abs(out - exact).max() < 0.05        # truncation error bound


def test_flat_jax_matches_exact(fitted):
    rng = np.random.default_rng(4)
    X = rng.lognormal(1, 1.5, size=(20, 12)).astype(np.float32)
    fj = FlatForestJax(fitted.to_flat())
    np.testing.assert_allclose(np.asarray(fj(X)), fitted.predict(X),
                               rtol=1e-5)


# --------------------------------------------------------------- attention

@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,causal", [
    (2, 4, 2, 64, 64, 32, True),
    (1, 2, 2, 33, 33, 16, True),
    (2, 8, 2, 17, 40, 8, False),
    (1, 4, 1, 128, 128, 64, True),
    (1, 2, 1, 16, 48, 8, True),       # chunked prefill against a cache
])
def test_flash_attention_vs_ref(B, Hq, Hkv, Sq, Skv, D, causal):
    rng = np.random.default_rng(hash((B, Hq, Sq)) % 2**31)
    q = jnp.asarray(rng.normal(size=(B, Hq, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=0.08, atol=0.08)


# ------------------------------------------------------------------- mamba

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 64, 3, 16, 8, 16),
    (1, 100, 2, 8, 4, 32),            # S not a multiple of chunk
    (2, 33, 1, 4, 8, 16),
    (1, 16, 2, 8, 4, 16),             # single chunk
])
def test_ssd_kernel_vs_ref(B, S, H, P, N, chunk):
    rng = np.random.default_rng(hash((B, S, H)) % 2**31)
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    alog = jnp.asarray(-np.abs(rng.normal(size=(B, S, H))) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y, h = ssd_scan(x, alog, Bm, Cm, chunk=chunk)
    yr, hr = ssd_ref(x, alog, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=2e-4, atol=2e-4)


def test_ssd_state_streaming():
    """Final state from one call == ref's final state (cache handoff)."""
    rng = np.random.default_rng(9)
    B, S, H, P, N = 1, 48, 2, 8, 4
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    alog = jnp.asarray(-np.abs(rng.normal(size=(B, S, H))) * 0.2, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    _, h = ssd_scan(x, alog, Bm, Cm, chunk=16)
    _, hr = ssd_ref(x, alog, Bm, Cm)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-4,
                               atol=2e-4)
