"""Cold-start portability tier (``core.transfer`` + its serving wiring).

The contract under test: a device the forests never trained on is served
IMMEDIATELY from its spec-sheet (or generic) analytical prior, probe
measurements refit the analytical coefficients and stack a forest on the
log-residuals, and accuracy converges toward full-forest MAPE — with the
probe ORDER chosen by feature-space coverage, deterministically
(PYTHONHASHSEED-independent, like the workload seeding and trace digests).
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.devices import DEVICE_MODELS, EDGE_DVFS, TPU_V5E
from repro.core.features import N_FEATURES
from repro.core.metrics import mape
from repro.core.simulate import (AnalyticalBaseline, WorkloadSpec,
                                 simulate_time_median_us)
from repro.core.transfer import (FittedAnalyticalModel, TransferConfig,
                                 TransferPredictor, generic_device_prior,
                                 select_probes)

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ------------------------------------------------------- synthetic ground truth

def _simulated_rows(device, n: int, seed: int):
    """(X, y): feature rows whose roofline columns drive the simulator —
    ground truth for a device with KNOWN physics but measurement noise."""
    rng = np.random.default_rng(seed)
    X, y = [], []
    for _ in range(n):
        flops = 10 ** rng.uniform(6, 12)
        gvol = 10 ** rng.uniform(4, 9)
        work = 10 ** rng.uniform(1, 7)
        special = flops * rng.uniform(0, 0.05)
        control = rng.uniform(0, 1e4)
        spec = WorkloadSpec(flops=flops, hbm_bytes=gvol, collective_bytes=0.0,
                            special_ops=special, control_ops=control,
                            work_items=work)
        t, _cov = simulate_time_median_us(spec, device, rng)
        row = np.zeros(N_FEATURES)
        row[0] = work
        row[1] = 1.0
        row[2] = flops + special + control
        row[3] = flops
        row[4] = special
        row[6] = control
        row[8] = gvol
        row[11] = flops / max(gvol, 1.0)
        X.append(row)
        y.append(t)
    return np.stack(X), np.asarray(y)


# ------------------------------------------------------------- probe selection

def test_select_probes_prefix_and_uniqueness():
    X = np.random.default_rng(3).lognormal(1.0, 2.0, size=(50, N_FEATURES))
    full = select_probes(X, 20)
    assert len(full) == 20
    assert len(np.unique(full)) == 20
    # the order IS the schedule: a smaller budget is a prefix
    assert np.array_equal(select_probes(X, 7), full[:7])
    # budget beyond the pool clips
    assert len(select_probes(X, 999)) == 50
    assert len(select_probes(X, 0)) == 0


def test_select_probes_covers_clusters():
    """Farthest-point traversal must visit every well-separated cluster
    before re-sampling any of them."""
    rng = np.random.default_rng(0)
    centers = np.array([1.0, 1e3, 1e6, 1e9])
    X = np.concatenate([
        c * rng.uniform(0.9, 1.1, size=(25, N_FEATURES)) for c in centers])
    chosen = select_probes(X, 4)
    assert sorted(c // 25 for c in chosen) == [0, 1, 2, 3]


_PROBE_SCRIPT = """
import sys; sys.path.insert(0, {src!r})
import numpy as np
from repro.core.transfer import select_probes
X = np.random.default_rng(11).lognormal(1.0, 2.0, size=(80, 12))
print(",".join(map(str, select_probes(X, 32))))
"""


def _probes_in_subprocess(hashseed: str) -> str:
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = hashseed
    out = subprocess.run(
        [sys.executable, "-c", _PROBE_SCRIPT.format(src=SRC)],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_select_probes_identical_across_hash_seeds():
    """Probe schedules from interpreters with different hash salts are
    identical — a new device calibrated on two hosts measures the SAME
    kernels in the SAME order."""
    a = _probes_in_subprocess("0")
    b = _probes_in_subprocess("4242")
    assert a and a == b


# ------------------------------------------------- fitted analytical model

def test_prior_matches_spec_roofline_scale():
    """Day zero = spec-sheet physics: within a small factor of the static
    AnalyticalBaseline (the fitted model adds occupancy terms, so exact
    equality is not expected — wild divergence is a bug)."""
    X, _ = _simulated_rows(TPU_V5E, 30, seed=5)
    fam = FittedAnalyticalModel(TPU_V5E)
    am = AnalyticalBaseline(TPU_V5E).predict(X)
    ratio = fam.predict(X) / am
    assert np.isfinite(ratio).all()
    assert (ratio > 0.2).all() and (ratio < 60.0).all()


def test_fit_never_produces_negative_coefficients():
    rng = np.random.default_rng(9)
    X = rng.lognormal(2.0, 2.0, size=(40, N_FEATURES))
    # adversarial targets uncorrelated with the basis
    y = rng.lognormal(3.0, 2.0, size=40)
    fam = FittedAnalyticalModel(TPU_V5E).fit(X, y)
    assert (fam.beta >= 0.0).all()
    assert (fam.predict(X) > 0.0).all()


def test_fit_recovers_rescaled_hardware():
    """A device whose real throughput is 3x below spec: the fit must move
    the compute multiplier toward ~3 and cut relative error vs. prior."""
    X, y = _simulated_rows(TPU_V5E, 60, seed=2)
    fam0 = FittedAnalyticalModel(TPU_V5E)
    fam = FittedAnalyticalModel(TPU_V5E).fit(X, 3.0 * y)
    m_prior = mape(3.0 * y, fam0.predict(X))
    m_fit = mape(3.0 * y, fam.predict(X))
    assert m_fit < m_prior
    assert fam.beta[1] > 1.5 or fam.beta[0] > 1.5  # scale went somewhere real


# --------------------------------------------- calibrate/observe convergence

def test_coldstart_convergence_beats_prior():
    """The ISSUE 9 acceptance shape, in-test: hardware that runs 3x below
    its spec sheet -> observe probes one at a time -> the hybrid beats the
    day-zero prior after K samples, with the residual forest ACTIVE and
    beating the fitted-analytical-only ablation."""
    Xp, yp = _simulated_rows(TPU_V5E, 60, seed=7)
    Xev, yev = _simulated_rows(TPU_V5E, 40, seed=8)
    yp, yev = 3.0 * yp, 3.0 * yev       # real silicon underdelivers 3x
    tp = TransferPredictor(TPU_V5E)
    assert tp.mode == "prior"
    m_day0 = mape(yev, tp.predict(Xev))

    order = select_probes(Xp, 48)
    for i in order:
        tp.observe(Xp[i], float(yp[i]))
    assert tp.mode == "hybrid"
    m_final = mape(yev, tp.predict(Xev))
    assert m_final < 0.5 * m_day0, (m_day0, m_final)

    # ...and the forest residual earns its keep over analytical-only
    ana_only = TransferPredictor(
        TPU_V5E, config=TransferConfig(min_forest_samples=10 ** 9))
    for i in order:
        ana_only.observe(Xp[i], float(yp[i]))
    assert ana_only.mode == "fitted"
    m_ana = mape(yev, ana_only.predict(Xev))
    assert m_final < 0.9 * m_ana, (m_ana, m_final)


def test_calibrate_bulk_equals_observe_streamed_mode():
    Xp, yp = _simulated_rows(TPU_V5E, 24, seed=1)
    bulk = TransferPredictor(TPU_V5E)
    bulk.calibrate((Xp, yp))
    assert bulk.mode == "hybrid"
    st = bulk.stats_snapshot()
    assert st.n_observed == 24
    assert st.forest_refits >= 1
    # re-target from generic prior to the real spec resets and refits
    generic = TransferPredictor("mystery")
    generic.calibrate((Xp, yp), device=TPU_V5E)
    assert generic.device.name == "tpu-v5e"
    assert generic.stats_snapshot().n_observed == 24


def test_log_output_matches_linear_output():
    X, y = _simulated_rows(TPU_V5E, 16, seed=4)
    lin = TransferPredictor(TPU_V5E)
    log = TransferPredictor(TPU_V5E, log_output=True)
    lin.calibrate((X, y))
    log.calibrate((X, y))
    np.testing.assert_allclose(np.exp(log.predict(X)), lin.predict(X),
                               rtol=1e-10)


def test_generic_prior_is_midrange():
    g = generic_device_prior("whatever")
    peaks = sorted(d.peak_flops for d in DEVICE_MODELS.values() if d.simulated)
    assert peaks[0] < g.peak_flops < peaks[-1]
    # unknown names resolve to it, known names to the zoo entry
    assert TransferPredictor("no-such-chip").device.clazz == "unknown"
    assert TransferPredictor("tpu-v4").device is DEVICE_MODELS["tpu-v4"]


def test_to_forest_graduation():
    Xp, yp = _simulated_rows(TPU_V5E, 30, seed=6)
    tp = TransferPredictor(TPU_V5E)
    tp.calibrate((Xp, yp))
    est = tp.to_forest()
    pred = np.exp(est.predict(Xp.astype(np.float32)))
    assert mape(yp, pred) < 60.0      # a real fit, not garbage
    with pytest.raises(ValueError):
        TransferPredictor(TPU_V5E).to_forest()


# ------------------------------------------------------------ serving wiring

def test_uncalibrated_device_serves_through_cluster_frontend():
    """A brand-new DeviceModel is admitted to the pool and answers through
    the full cluster path with zero training samples."""
    from repro.cluster.frontend import ClusterFrontend
    from repro.cluster.replicas import ReplicaPool
    from repro.serve.backend import build_transfer_engine, calibration_rows

    eng = build_transfer_engine("just-unboxed-accelerator")
    assert eng.n_features == N_FEATURES
    pool = ReplicaPool({"cold": eng},
                       probe_X=calibration_rows(4, N_FEATURES),
                       check_interval_s=60.0)
    with ClusterFrontend(pool, max_queue=16) as fe:
        val = fe.submit(calibration_rows(1, N_FEATURES)[0]).result(timeout=10)
        assert np.isfinite(val) and val > 0.0
        X = calibration_rows(5, N_FEATURES)
        out = fe.submit_batch(X).result(timeout=10)
        assert out.shape == (5,) and (out > 0.0).all()
        # observing mid-serve is safe (refits publish under the lock)
        eng.observe(X[0].astype(np.float64), 123.0)
        val2 = fe.submit(X[1]).result(timeout=10)
        assert np.isfinite(val2) and val2 > 0.0


def test_stats_snapshot_and_calibration_mape_gauge():
    """observe() feeds CalibrationMonitor with the PRE-update prediction:
    the calibration.mape{device,target} gauge tracks convergence and
    stats_snapshot() exposes the refit counters."""
    from repro.obs.calibration import CalibrationMonitor
    from repro.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    mon = CalibrationMonitor(reg, alpha=0.5)
    Xp, yp = _simulated_rows(EDGE_DVFS, 24, seed=3)
    tp = TransferPredictor("fresh-device", monitor=mon)
    assert mon.mape("fresh-device", "time_us") is None
    for i in range(len(yp)):
        tp.observe(Xp[i], float(yp[i]), kernel=f"k{i % 3}")
    live = mon.mape("fresh-device", "time_us")
    assert live is not None and np.isfinite(live)
    assert mon.mape_by_kernel("fresh-device", "time_us")
    text = reg.render_prometheus()
    assert "calibration.mape" in text.replace("_", ".")

    st = tp.stats_snapshot()
    assert st.device == "fresh-device" and st.target == "time_us"
    assert st.mode == "hybrid"
    assert st.n_observed == 24
    assert st.analytical_refits == 24
    assert 1 <= st.forest_refits <= 24
    assert st.generation == 24
    assert len(st.beta) == 5
    assert st.as_dict()["mode"] == "hybrid"


def test_ingest_store_streams_probes():
    """StreamingCollector -> DatasetStore -> ingest_store: the documented
    live-calibration loop, end to end on real (tiny) workloads."""
    from repro.core.dataset import DatasetStore
    from repro.workloads.stream import StreamingCollector
    from repro.workloads.suite import suite

    store = DatasetStore()
    workloads = suite(sizes=("s",))[:3]
    tp = TransferPredictor(TPU_V5E)
    coll = StreamingCollector(
        store, workloads, repeats=2, measure=False, seed=0,
        on_chunk=lambda _v, _n: tp.ingest_store(store))
    n = coll.run_sync()
    assert n == 3
    st = tp.stats_snapshot()
    assert st.n_observed == 3 and st.mode == "fitted"
    # idempotent: nothing new in the store, nothing ingested
    assert tp.ingest_store(store) == 0
    assert tp.stats_snapshot().n_observed == 3
    assert (tp.predict(np.stack([s.features for s in store.raw()[0]]))
            > 0).all()


# ------------------------------------------------------- ingestion regressions

def _store_of(X, y, device=TPU_V5E, poison=()):
    """A DatasetStore of (X, y) samples targeting ``device``; indices in
    ``poison`` get a feature vector of the wrong width (an ingestion-time
    failure, like a schema change mid-campaign)."""
    from repro.core.dataset import DatasetStore, Sample

    store = DatasetStore()
    store.extend([
        Sample(app="t", kernel=f"k{i}", variant="s",
               features=np.ones(3) if i in poison else X[i],
               targets={device.name: {"time_us": float(y[i])}})
        for i in range(len(y))])
    return store


def test_ingest_store_poisoned_sample_keeps_tail():
    """Regression: a sample that fails mid-ingest must not lose the TAIL of
    the store behind it (the old code advanced the high-water mark to
    len(samples) up front, so an exception skipped everything after it)."""
    X, y = _simulated_rows(TPU_V5E, 12, seed=7)
    store = _store_of(X, y, poison={4})
    tp = TransferPredictor(TPU_V5E)
    n = tp.ingest_store(store)       # must not raise, must not stop at 4
    assert n == 11
    st = tp.stats_snapshot()
    assert st.n_observed == 11       # samples AFTER the poisoned one landed
    assert st.ingested == 12         # watermark covers the whole store
    assert st.ingest_errors == 1
    # idempotent: the poisoned sample is not retried forever
    assert tp.ingest_store(store) == 0
    assert tp.stats_snapshot().ingest_errors == 1


def test_calibrate_retarget_replays_store_history():
    """Regression: calibrate(device=...) resets the ingest high-water mark,
    so a follow-up ingest_store recovers the FULL history onto the new
    device model (the old code kept the mark, replaying nothing)."""
    import dataclasses

    real_spec = dataclasses.replace(TPU_V5E, name="mystery")
    X, y = _simulated_rows(real_spec, 16, seed=8)
    store = _store_of(X, y, device=real_spec)

    tp = TransferPredictor("mystery")      # generic prior, day zero
    assert tp.ingest_store(store) == 16
    before = tp.stats_snapshot()
    assert before.n_observed == 16 and before.ingested == 16

    tp.calibrate([], device=real_spec)     # spec sheet lands mid-serve
    st = tp.stats_snapshot()
    assert st.n_observed == 0 and st.ingested == 0   # fresh start
    assert tp.ingest_store(store) == 16    # history replays, not 0
    st = tp.stats_snapshot()
    assert st.n_observed == 16 and st.mode == "hybrid"


def test_observe_calls_are_atomic_under_concurrency():
    """Stress: concurrent observers (and a mid-flight re-target) never
    crash, never lose a sample, and every observe call returns a DISTINCT
    generation that includes its own samples."""
    import threading

    X, y = _simulated_rows(TPU_V5E, 64, seed=9)
    tp = TransferPredictor(TPU_V5E)
    gens: list[int] = []
    gens_lock = threading.Lock()
    errs: list[BaseException] = []

    def worker(rows):
        try:
            for i in rows:
                g = tp.observe(X[i], float(y[i]))
                with gens_lock:
                    gens.append(g)
        except BaseException as e:   # pragma: no cover - fails the test
            errs.append(e)

    threads = [threading.Thread(target=worker,
                                args=(range(k, 64, 4),)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(gens) == 64
    assert len(set(gens)) == 64            # fully serialized refits
    st = tp.stats_snapshot()
    assert st.n_observed == 64
    assert st.generation == max(gens)
    assert np.isfinite(tp.predict(X)).all()
