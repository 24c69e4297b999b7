"""Streaming collection + refresh pipeline: snapshot determinism (same seed
=> same dataset, streamed == batch-collected), the deterministic
over-representation cap under incremental appends, the versioned store, the
background refresher, and — the acceptance bar — hot-swaps landing during a
concurrent prediction stream never yielding a mixed-generation batch."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataset import (Dataset, DatasetStore, Sample,
                                cap_overrepresented)
from repro.core.forest import ExtraTreesRegressor
from repro.serve import EngineRefresher, ForestEngine, single_device_fit_fn
from repro.workloads.collect import collect
from repro.workloads.stream import StreamingCollector, iter_samples
from repro.workloads.suite import Workload

N_F = 8


def _workloads(n=5):
    out = []
    for i in range(n):
        rows = 8 * (i + 1)
        a = jnp.arange(float(rows * 4)).reshape(rows, 4).astype(jnp.float32)
        out.append(Workload("toy", f"k{i}", f"n{rows}",
                            lambda a: (a * 2.0 + 1.0).sum(axis=1), (a,),
                            float(rows)))
    return out


def _sample(i: int, kernel: str = "k") -> Sample:
    return Sample(app="app", kernel=kernel, variant=f"v{i}",
                  features=np.full(N_F, float(i)),
                  targets={"d": {"time_us": float(i + 1)}})


# ------------------------------------------------------------- determinism

def test_streamed_samples_equal_batch_collect():
    wls = _workloads()
    streamed = list(iter_samples(wls, repeats=3, measure=False, seed=7))
    batch = collect(wls, repeats=3, measure=False, seed=7)
    assert len(streamed) == len(batch.samples)
    for a, b in zip(streamed, batch.samples):
        assert a.to_json() == b.to_json()


def test_streaming_collector_snapshot_determinism():
    wls = _workloads()
    snaps = []
    for chunk in (1, 3):                       # chunking must not matter
        store = DatasetStore(max_per_group=100, seed=0)
        c = StreamingCollector(store, wls, repeats=3, measure=False,
                               seed=11, chunk_size=chunk)
        assert c.run_sync() == len(wls)
        snaps.append(store.snapshot())
    a, b = snaps
    assert [s.to_json() for s in a.dataset.samples] == \
           [s.to_json() for s in b.dataset.samples]


def test_streaming_collector_background_thread():
    wls = _workloads()
    store = DatasetStore(max_per_group=100, seed=0)
    chunks = []
    c = StreamingCollector(store, wls, repeats=2, measure=False, seed=0,
                           chunk_size=2,
                           on_chunk=lambda v, n: chunks.append((v, n)))
    with c:
        assert c.wait(timeout=120)
    assert c.error is None
    assert c.collected == len(wls)
    assert len(store) == len(wls)
    assert store.version == len(chunks)        # one version bump per chunk
    assert sum(n for _, n in chunks) == len(wls)


# ------------------------------------------------------- over-representation

def test_cap_deterministic_and_group_local():
    big = [_sample(i, "hot") for i in range(60)]
    small = [_sample(i, "cold") for i in range(5)]
    kept1 = cap_overrepresented(big + small, max_per_group=20, seed=0)
    kept2 = cap_overrepresented(big + small, max_per_group=20, seed=0)
    assert [s.variant for s in kept1] == [s.variant for s in kept2]
    # the under-cap group is untouched, in arrival order
    assert [s.variant for s in kept1 if s.kernel == "cold"] == \
           [s.variant for s in small]
    assert sum(s.kernel == "hot" for s in kept1) == 20
    # a different seed picks a different subset
    kept3 = cap_overrepresented(big + small, max_per_group=20, seed=1)
    assert [s.variant for s in kept3] != [s.variant for s in kept1]


def test_overrep_cap_under_incremental_appends():
    all_samples = [_sample(i, "hot") for i in range(50)]
    chunked = DatasetStore(max_per_group=20, seed=0)
    for i in range(0, 50, 7):
        chunked.extend(all_samples[i:i + 7])
        snap = chunked.snapshot()
        n_hot = sum(s.kernel == "hot" for s in snap.dataset.samples)
        assert n_hot <= 20                     # cap holds at EVERY version
        assert snap.n_total == min(i + 7, 50)
    oneshot = DatasetStore(max_per_group=20, seed=0)
    oneshot.extend(all_samples)
    assert [s.to_json() for s in chunked.snapshot().dataset.samples] == \
           [s.to_json() for s in oneshot.snapshot().dataset.samples]


# ------------------------------------------------------------------- store

def test_store_versioning_and_snapshot_immutability():
    store = DatasetStore(max_per_group=10, seed=0)
    assert store.version == 0 and len(store) == 0
    assert store.append(_sample(0)) == 1
    snap1 = store.snapshot()
    assert snap1 is store.snapshot()           # cached at same version
    store.extend([_sample(1), _sample(2)])
    assert store.version == 2
    assert len(snap1.dataset) == 1             # old snapshot untouched
    assert len(store.snapshot().dataset) == 3
    assert store.extend([]) == 2               # empty append: no version bump


def test_store_save_roundtrip(tmp_path):
    store = DatasetStore(max_per_group=10, seed=0,
                         samples=[_sample(i) for i in range(4)])
    snap = store.save(tmp_path / "ds.json")
    assert snap.version == 1
    loaded = Dataset.load(tmp_path / "ds.json")
    assert len(loaded) == 4


# --------------------------------------------------------------- refresher

def _const_est(X: np.ndarray, c: float) -> ExtraTreesRegressor:
    """Forest whose every prediction is EXACTLY c (constant target => the
    root is a pure leaf) — makes model generations observable per row."""
    return ExtraTreesRegressor(n_estimators=4, seed=0).fit(
        X, np.full(X.shape[0], c))


def test_refresher_refits_on_new_snapshots():
    rng = np.random.default_rng(0)
    X = rng.lognormal(1.0, 1.0, (32, N_F)).astype(np.float32)
    store = DatasetStore(max_per_group=100, seed=0)
    eng = ForestEngine(_const_est(X, 0.0), backend="flat-numpy")
    ref = EngineRefresher(store, eng, lambda ds: _const_est(X, float(len(ds))),
                          min_samples=1)
    assert ref.refresh_once() is None          # empty store: nothing to do
    store.append(_sample(0))
    assert ref.refresh_once() == store.version
    assert eng.generation == 1
    assert eng.predict(X[:4])[0] == 1.0        # trained on the 1-sample set
    assert ref.refresh_once() is None          # no new version
    assert ref.stats.refreshes == 1 and ref.stats.skipped == 2
    store.extend([_sample(1), _sample(2)])
    assert ref.refresh_once() == store.version
    assert eng.predict(X[:4])[0] == 3.0
    eng.close()


def test_refresher_blacklists_failing_version():
    """A deterministically bad snapshot must not become a refit hot-loop:
    the failed version is skipped until the store advances."""
    rng = np.random.default_rng(0)
    X = rng.lognormal(1.0, 1.0, (16, N_F)).astype(np.float32)
    store = DatasetStore(max_per_group=100, seed=0)
    store.append(_sample(0))
    eng = ForestEngine(_const_est(X, 0.0), backend="flat-numpy")
    calls = []

    def flaky_fit(ds):
        calls.append(len(ds))
        if len(ds) < 2:
            raise RuntimeError("not enough signal")
        return _const_est(X, float(len(ds)))

    ref = EngineRefresher(store, eng, flaky_fit, min_samples=1)
    with pytest.raises(RuntimeError):
        ref.refresh_once()
    assert ref.stats.errors == 1
    assert ref.stats.failed_version == store.version
    assert ref.refresh_once() is None          # blacklisted, NOT retried
    assert len(calls) == 1
    assert eng.generation == 0                 # old generation kept serving
    store.append(_sample(1))                   # store advances -> retry
    assert ref.refresh_once() == store.version
    assert eng.generation == 1 and len(calls) == 2
    eng.close()


def test_refresher_background_thread_and_fit_fn_helper():
    wls = _workloads(4)
    store = DatasetStore(max_per_group=100, seed=0)
    store.extend(list(iter_samples(wls[:2], repeats=2, measure=False,
                                   seed=0)))
    fit = single_device_fit_fn("tpu-v5e", n_estimators=8)
    eng = ForestEngine(fit(store.snapshot().dataset), backend="flat-numpy")
    with EngineRefresher(store, eng, fit, min_samples=1, poll_s=0.01) as ref:
        store.extend(list(iter_samples(wls[2:], repeats=2, measure=False,
                                       seed=1)))
        deadline = time.monotonic() + 30
        while ref.stats.last_version < store.version:
            assert time.monotonic() < deadline
            time.sleep(0.01)
    assert ref.stats.refreshes >= 1
    assert eng.generation >= 1
    eng.close()


def test_hot_swap_never_mixes_generations_under_load():
    """Acceptance: swaps land mid-storm; every answered batch must be
    uniformly one model generation. Constant-prediction forests make a mixed
    batch directly visible as >1 distinct value in one result."""
    rng = np.random.default_rng(1)
    X = rng.lognormal(1.0, 1.0, (48, N_F)).astype(np.float32)
    store = DatasetStore(max_per_group=100, seed=0)
    store.append(_sample(0))
    eng = ForestEngine(_const_est(X, float(len(store))), backend="flat-numpy",
                       max_batch=16, max_delay_ms=0.5, cache_size=4096)
    ref = EngineRefresher(store, eng, lambda ds: _const_est(X, float(len(ds))),
                          min_samples=1)

    stop = threading.Event()
    mixed, errors = [], []

    def client():
        try:
            while not stop.is_set():
                out = eng.predict(X)
                vals = np.unique(out)
                if vals.size != 1:
                    mixed.append(vals)
        except Exception as exc:               # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    n_swaps = 8
    for i in range(1, n_swaps + 1):
        time.sleep(0.02)
        store.append(_sample(i))
        assert ref.refresh_once() == store.version
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    assert not mixed, f"mixed-generation batches: {mixed[:3]}"
    assert eng.generation == n_swaps
    # post-swap steady state serves the latest generation only
    assert eng.predict(X)[0] == float(len(store))
    eng.close()
