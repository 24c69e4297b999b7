"""Serving-engine tests: cross-backend golden equivalence against the
tree-walk oracle, micro-batching invariance (N singles == one batch of N),
cache hit/eviction semantics, deadline-flush behavior, auto-selection,
hot-swap semantics, close() lifecycle under concurrency, and the scheduler
frontend."""
import threading
import time

import numpy as np
import pytest

from repro.core.forest import ExtraTreesRegressor
from repro.core.scheduler import DevicePredictor, predict_matrix, schedule
from repro.serve import (BACKENDS, EngineConfig, ForestEngine,
                         MultiDeviceEngine, build_backends)


def _data(seed=0, n=150, f=10):
    rng = np.random.default_rng(seed)
    X = rng.lognormal(1.0, 1.5, size=(n, f)).astype(np.float32)
    y = np.log(2 * X[:, 0] + 0.5 * X[:, 3] + 3.0)
    return X, y + 0.05 * rng.normal(size=n)


@pytest.fixture(scope="module")
def fitted():
    X, y = _data()
    # max_depth below the engine's dense_depth so dense/pallas are EXACT
    est = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=0).fit(X, y)
    return est, X, y


# ------------------------------------------------------- golden equivalence

@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_matches_tree_walk_oracle(fitted, backend):
    est, X, _ = fitted
    oracle = est.predict(X)
    with ForestEngine(est, EngineConfig(backend=backend,
                                        dense_depth=8)) as eng:
        pred = eng.predict(X)
    np.testing.assert_allclose(pred, oracle, rtol=1e-5, atol=1e-5)


def test_build_backends_rejects_unknown(fitted):
    est, _, _ = fitted
    with pytest.raises(ValueError):
        build_backends(est, only=("warp-drive",))


def _broken(*a, **k):
    raise RuntimeError("broken path")


@pytest.mark.parametrize("backend", ["flat-jax", "auto"])
def test_backend_failing_to_build_raises(fitted, monkeypatch, backend):
    """A path that fails to BUILD is an error, whether it was named or is
    one of auto mode's candidates: it is never silently dropped."""
    import repro.core.forest_jax as fjx
    est, _, _ = fitted
    monkeypatch.setattr(fjx, "FlatForestJax", _broken)
    with pytest.raises(RuntimeError, match="broken path"):
        ForestEngine(est, EngineConfig(backend=backend, calibration_iters=1))


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_backend_failing_to_run_raises(fitted, monkeypatch, backend):
    """A path that builds but fails to RUN raises: at the first prediction
    when named, during calibration in auto mode (no +inf score)."""
    import repro.kernels.forest.ops as ops
    est, X, _ = fitted
    monkeypatch.setattr(ops, "forest_predict_kernel", _broken)
    with pytest.raises(RuntimeError, match="broken path"):
        with ForestEngine(est, EngineConfig(backend=backend,
                                            calibration_iters=1)) as eng:
            eng.predict(X[:4])


@pytest.fixture(scope="module")
def deep():
    X, y = _data(seed=1)
    est = ExtraTreesRegressor(n_estimators=4, seed=0).fit(X, y)
    assert max(t.depth() for t in est.trees_) > 6
    return est, X


def test_auto_never_selects_truncating_path_for_deep_forest(deep):
    est, X = deep
    assert set(build_backends(est, dense_depth=6)) == {
        "tree-walk", "flat-numpy", "flat-jax"}
    with ForestEngine(est, EngineConfig(backend="auto", dense_depth=6,
                                        calibration_iters=1)) as eng:
        assert set(eng.calibration) == {"tree-walk", "flat-numpy",
                                        "flat-jax"}
        np.testing.assert_allclose(eng.predict(X), est.predict(X),
                                   rtol=1e-5, atol=1e-5)
    # a candidate list of truncating paths only leaves auto nothing
    with pytest.raises(RuntimeError, match="no backend"):
        ForestEngine(est, EngineConfig(backend="auto", dense_depth=6,
                                       backends=("dense-jax", "pallas")))
    # named explicitly, a truncating path is still served, as documented
    with ForestEngine(est, EngineConfig(backend="dense-jax",
                                        dense_depth=6)) as eng:
        assert eng.backend == "dense-jax"


def test_swap_to_deep_forest_drops_truncating_path(fitted, deep):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="auto", dense_depth=6,
                                        backends=("dense-jax", "flat-numpy"),
                                        calibration_iters=1)) as eng:
        assert set(eng.calibration) == {"dense-jax", "flat-numpy"}
        eng.swap_estimator(deep[0])
        assert eng.backend == "flat-numpy"
        np.testing.assert_allclose(eng.predict(X[:8]), deep[0].predict(X[:8]),
                                   rtol=1e-5, atol=1e-5)


def test_auto_selection_runs_all_candidates(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="auto",
                                        calibration_iters=1)) as eng:
        assert eng.backend in BACKENDS
        assert set(eng.calibration) == set(BACKENDS)
        assert np.isfinite(eng.calibration[eng.backend])
        np.testing.assert_allclose(eng.predict(X), est.predict(X),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------- batching invariance

def test_batched_equals_singles(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy",
                                        cache_size=0)) as eng:
        batched = eng.predict(X[:32])
        singles = np.array([eng.predict(X[i])[0] for i in range(32)])
    np.testing.assert_allclose(batched, singles, rtol=1e-12)


def test_async_singles_equal_batch(fitted):
    est, X, _ = fitted
    n = 24
    with ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=n,
                                        max_delay_ms=500.0)) as eng:
        futs = [eng.predict_async(X[i]) for i in range(n)]
        got = np.array([f.result(timeout=10) for f in futs])
        # exactly max_batch pending -> one size-triggered forest call
        assert eng.stats.flushes_size == 1
        assert eng.stats.batches == 1
    with ForestEngine(est, EngineConfig(backend="flat-numpy",
                                        cache_size=0)) as ref:
        np.testing.assert_allclose(got, ref.predict(X[:n]), rtol=1e-12)


def test_async_validates_feature_length(fitted):
    est, _, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy")) as eng:
        with pytest.raises(ValueError):
            eng.predict_async(np.zeros(3, dtype=np.float32))


# ------------------------------------------------------------------- cache

def test_cache_hits_on_repeat(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy",
                                        cache_size=1024)) as eng:
        p1 = eng.predict(X[:20])
        assert eng.stats.cache_misses == 20
        p2 = eng.predict(X[:20])
        assert eng.stats.cache_hits == 20
        assert eng.stats.batches == 1          # second call hit no backend
    np.testing.assert_array_equal(p1, p2)


def test_cache_dedupes_within_one_batch(fitted):
    est, X, _ = fitted
    dup = np.repeat(X[:5], 3, axis=0)
    with ForestEngine(est, EngineConfig(backend="flat-numpy")) as eng:
        p = eng.predict(dup)
        assert eng.stats.backend_rows == 5     # 15 rows, 5 unique
    np.testing.assert_array_equal(p[0::3], p[1::3])


def test_cache_eviction_lru(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy",
                                        cache_size=8)) as eng:
        eng.predict(X[:16])
        assert eng.cache_len() == 8
        eng.predict(X[8:16])                   # the 8 survivors (LRU)
        assert eng.stats.cache_hits == 8
        eng.predict(X[:8])                     # evicted -> misses again
        assert eng.stats.cache_misses == 16 + 8


def test_cache_disabled(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy",
                                        cache_size=0)) as eng:
        eng.predict(X[:4])
        eng.predict(X[:4])
        assert eng.cache_len() == 0
        assert eng.stats.batches == 2


def test_async_cache_hit_resolves_immediately(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=64,
                                        max_delay_ms=10_000.0)) as eng:
        warm = eng.predict(X[0])[0]
        fut = eng.predict_async(X[0])          # no flush can fire for 10 s
        assert fut.done()
        assert fut.result() == warm


# ---------------------------------------------------------- deadline flush

def test_deadline_flush(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=64,
                                        max_delay_ms=30.0)) as eng:
        t0 = time.monotonic()
        fut = eng.predict_async(X[0])          # 1 pending << max_batch
        got = fut.result(timeout=10)
        elapsed = time.monotonic() - t0
        assert eng.stats.flushes_deadline == 1
        assert eng.stats.flushes_size == 0
    assert elapsed < 5.0                       # deadline, not the 64th request
    np.testing.assert_allclose(got, est.predict(X[:1])[0], rtol=1e-5)


def test_manual_flush(fitted):
    est, X, _ = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=64,
                                        max_delay_ms=10_000.0)) as eng:
        futs = [eng.predict_async(X[i]) for i in range(3)]
        assert not any(f.done() for f in futs)
        assert eng.flush() == 3
        assert all(f.done() for f in futs)


def test_close_flushes_pending(fitted):
    est, X, _ = fitted
    eng = ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=64,
                                         max_delay_ms=10_000.0))
    fut = eng.predict_async(X[0])
    eng.close()
    assert fut.done()
    with pytest.raises(RuntimeError):
        eng.predict_async(X[0])


def test_close_idempotent_and_joins_worker(fitted):
    est, X, _ = fitted
    eng = ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=64,
                                         max_delay_ms=10_000.0))
    eng.predict_async(X[0])
    worker = eng._worker
    assert worker is not None and worker.is_alive()
    eng.close()
    assert not worker.is_alive()               # joined, not leaked
    flushes = eng.stats.flushes_manual
    eng.close()                                # second close: clean no-op
    eng.close()
    assert eng.stats.flushes_manual == flushes


def test_close_races_predict_async(fitted):
    """predict_async storm racing close(): every future must either resolve
    or the submit must raise the closed error — nothing hangs, no thread
    leaks, close stays idempotent under concurrency."""
    est, X, _ = fitted
    eng = ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=8,
                                         max_delay_ms=0.2, cache_size=0))
    futs, rejected = [], []
    stop = threading.Event()

    def spam():
        i = 0
        while not stop.is_set():
            try:
                futs.append(eng.predict_async(X[i % 32]))
            except RuntimeError:
                rejected.append(i)
                return
            i += 1

    threads = [threading.Thread(target=spam) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    closers = [threading.Thread(target=eng.close) for _ in range(3)]
    for t in closers:
        t.start()
    stop.set()
    for t in threads + closers:
        t.join(timeout=30)
        assert not t.is_alive()
    for f in futs:
        assert f.done()
        f.result(timeout=1)                    # resolved, not dropped


# ---------------------------------------------------------------- hot-swap

def test_swap_estimator_invalidates_cache_and_bumps_generation(fitted):
    est, X, y = fitted
    est2 = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=9).fit(
        X, y + 2.0)
    with ForestEngine(est, EngineConfig(backend="flat-numpy")) as eng:
        assert eng.generation == 0
        p1 = eng.predict(X[:16])
        assert eng.cache_len() == 16
        gen = eng.swap_estimator(est2)
        assert gen == 1
        assert eng.stats.generation == 1 and eng.stats.swaps == 1
        assert eng.cache_len() == 0            # stale predictions dropped
        misses = eng.stats.cache_misses
        p2 = eng.predict(X[:16])
        assert eng.stats.cache_misses == misses + 16
        np.testing.assert_allclose(p2, est2.predict(X[:16]), rtol=1e-6)
        assert not np.allclose(p1, p2)


def test_swap_estimator_validates(fitted):
    est, X, y = fitted
    with ForestEngine(est, EngineConfig(backend="flat-numpy")) as eng:
        with pytest.raises(ValueError):
            eng.swap_estimator(ExtraTreesRegressor())      # unfitted
        wrong = ExtraTreesRegressor(n_estimators=2, seed=0).fit(
            X[:, :4], y)                                   # 4 != 10 features
        with pytest.raises(ValueError):
            eng.swap_estimator(wrong)
        assert eng.generation == 0             # failed swaps change nothing
    with pytest.raises(RuntimeError):
        eng.swap_estimator(est)                # closed engine refuses swaps


def test_async_requests_span_swap(fitted):
    est, X, y = fitted
    est2 = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=9).fit(
        X, y + 2.0)
    with ForestEngine(est, EngineConfig(backend="flat-numpy", max_batch=64,
                                        max_delay_ms=10_000.0)) as eng:
        futs = [eng.predict_async(X[i]) for i in range(6)]
        eng.swap_estimator(est2)
        eng.flush()
        got = np.array([f.result(timeout=10) for f in futs])
        # queued BEFORE the swap, flushed AFTER: answered by the new
        # generation, uniformly (pending requests survive the swap)
        np.testing.assert_allclose(got, est2.predict(X[:6]), rtol=1e-6)


# -------------------------------------------------- multi-device / scheduler

@pytest.fixture(scope="module")
def multi(fitted):
    est, X, y = fitted
    est2 = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=1).fit(
        X, y + np.log(3.0))                    # a ~3x slower device
    est_p = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=2).fit(
        X, np.full(len(y), 75.0))
    mde = MultiDeviceEngine.from_fits(
        {"fast": (est, est_p), "slow": (est2, None)},
        counts={"fast": 2},
        config=EngineConfig(backend="flat-numpy"))
    yield mde, est, est2, X
    mde.close()


def test_price_matrix_matches_direct_predictions(multi):
    mde, est, est2, X = multi
    T, P = mde.price(X[:30])
    assert T.shape == P.shape == (30, 2)
    np.testing.assert_allclose(T[:, 0], np.exp(est.predict(X[:30])),
                               rtol=1e-6)
    np.testing.assert_allclose(T[:, 1], np.exp(est2.predict(X[:30])),
                               rtol=1e-6)
    assert np.allclose(P[:, 1], 1.0)           # no power model -> unit power
    assert (P[:, 0] > 1.0).all()


def test_scheduler_consumes_engine_frontend(multi):
    mde, _, _, X = multi
    T_eng, P_eng = predict_matrix(X[:40], mde)
    T_dp, P_dp = predict_matrix(X[:40], mde.to_device_predictors())
    np.testing.assert_allclose(T_eng, T_dp)
    np.testing.assert_allclose(P_eng, P_dp)

    sched = schedule(X[:40], mde)
    assert len(sched.assignments) == 40
    devices = {a.device for a in sched.assignments}
    assert devices <= {"fast", "slow"}
    # ~3x faster device with 2 queues should carry most of the load
    fast_share = np.mean([a.device == "fast" for a in sched.assignments])
    assert fast_share > 0.5


def test_legacy_callable_predictors_still_work(fitted):
    est, X, _ = fitted
    devs = [DevicePredictor("a", est.predict, None, log_time=True),
            DevicePredictor("b", lambda Z: est.predict(Z) + 1.0, None)]
    T, _ = predict_matrix(X[:10], devs)
    assert (T[:, 1] > T[:, 0]).all()


def test_multi_device_swap_fits(fitted):
    est, X, y = fitted
    est2 = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=1).fit(
        X, y + np.log(3.0))
    est_new = ExtraTreesRegressor(n_estimators=8, max_depth=6, seed=7).fit(
        X, y + 1.0)
    mde = MultiDeviceEngine.from_fits(
        {"fast": (est, None), "slow": (est2, None)},
        config=EngineConfig(backend="flat-numpy"))
    try:
        T_before, _ = mde.price(X[:10])
        gens = mde.swap_fits({"fast": (est_new, None)})
        assert gens == {"fast": 1}
        assert mde.generations() == {"fast": 1, "slow": 0}
        T_after, _ = mde.price(X[:10])
        np.testing.assert_allclose(T_after[:, 0],
                                   np.exp(est_new.predict(X[:10])), rtol=1e-6)
        np.testing.assert_allclose(T_after[:, 1], T_before[:, 1])  # untouched
        with pytest.raises(KeyError):
            mde.swap_fits({"nope": (est_new, None)})
        # atomicity: one bad fit rejects the WHOLE batch — no device swaps
        wrong = ExtraTreesRegressor(n_estimators=2, seed=0).fit(X[:, :4], y)
        with pytest.raises(ValueError):
            mde.swap_fits({"fast": (est, None), "slow": (wrong, None)})
        assert mde.generations() == {"fast": 1, "slow": 0}
    finally:
        mde.close()


def test_freq_scale_reprices_time_and_power(fitted):
    est, X, _ = fitted
    p_fn = lambda Z: np.full(Z.shape[0], 10.0)
    base = DevicePredictor("d", est.predict, p_fn, log_time=True)
    slow = DevicePredictor("d", est.predict, p_fn, log_time=True,
                           freq_scale=0.5)
    T1, P1 = predict_matrix(X[:8], [base])
    T2, P2 = predict_matrix(X[:8], [slow])
    np.testing.assert_allclose(T2, T1 * 2.0)       # t ∝ 1/f
    np.testing.assert_allclose(P2, P1 * 0.125)     # P ∝ f^3
    with pytest.raises(ValueError):
        predict_matrix(X[:8], [DevicePredictor("d", est.predict,
                                               freq_scale=0.0)])
