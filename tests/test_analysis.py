"""HLO analyzer, simulator/power-model, dataset, autotune, scheduler tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataset import Dataset
from repro.core.devices import DEVICE_MODELS, EDGE_DVFS, TPU_V5E
from repro.core.features import FEATURE_NAMES, LaunchConfig, extract
from repro.core.hlo_analysis import analyze_hlo_text
from repro.core.power import simulate_power_w
from repro.core.scheduler import DevicePredictor, schedule, speedup_vs_baseline
from repro.core.simulate import WorkloadSpec, simulate_time_us


# ------------------------------------------------------------ hlo analysis

def test_hlo_flops_trip_weighted():
    L = 5

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        c, _ = jax.lax.scan(body, x, None, length=L)
        return c.sum()

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    costs = analyze_hlo_text(compiled.as_text())
    expect = L * 2 * 8 * 64 * 64
    assert costs.flops == pytest.approx(expect, rel=0.2)
    assert costs.while_trips and costs.while_trips[0] == L
    # XLA's own cost_analysis counts the body ONCE — our analyzer corrects it
    xla = compiled.cost_analysis()["flops"]
    assert costs.flops > 2 * xla


def test_hlo_grad_flops_about_3x():
    def f(x, w):
        return jnp.tanh(x @ w).sum()

    g = jax.grad(f, argnums=1)
    args = (jax.ShapeDtypeStruct((32, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32))
    fwd = analyze_hlo_text(jax.jit(f).lower(*args).compile().as_text()).flops
    bwd = analyze_hlo_text(jax.jit(g).lower(*args).compile().as_text()).flops
    assert 1.5 * fwd < bwd < 4.5 * fwd


# -------------------------------------------------------- simulator / power

def _spec(flops=1e9, mem=1e6, work=1e5):
    return WorkloadSpec(flops=flops, hbm_bytes=mem, collective_bytes=0,
                        special_ops=0, control_ops=0, work_items=work)


def test_sim_time_monotone_in_flops():
    rng = None
    t1 = simulate_time_us(_spec(flops=1e9), TPU_V5E, rng)
    t2 = simulate_time_us(_spec(flops=1e10), TPU_V5E, rng)
    assert t2 > t1


def test_sim_small_kernels_hit_latency_floor():
    t = simulate_time_us(_spec(flops=1e3, mem=1e3, work=10), TPU_V5E, None)
    assert t == pytest.approx(TPU_V5E.latency_floor_us, rel=0.5)


def test_sim_dvfs_device_noisier():
    rng1, rng2 = np.random.default_rng(0), np.random.default_rng(0)
    xs_srv = [simulate_time_us(_spec(), TPU_V5E, rng1) for _ in range(60)]
    xs_edge = [simulate_time_us(_spec(), EDGE_DVFS, rng2) for _ in range(60)]
    cov = lambda xs: np.std(xs) / np.mean(xs)
    assert cov(xs_edge) > 2 * cov(xs_srv)     # the GTX1650 effect


def test_power_within_bounds_and_monotone_in_utilization():
    for dev in DEVICE_MODELS.values():
        lo = simulate_power_w(_spec(work=1), dev, None)
        hi = simulate_power_w(_spec(flops=1e14, work=1e9), dev, None)
        assert dev.idle_w <= lo <= hi <= dev.peak_w * 1.05


def test_power_low_variance():
    rng = np.random.default_rng(0)
    xs = [simulate_power_w(_spec(), TPU_V5E, rng) for _ in range(50)]
    assert np.std(xs) / np.mean(xs) < 0.05     # paper Fig. 4


# ------------------------------------------------------------------ dataset

def test_dataset_roundtrip(tmp_path):
    ds = Dataset()
    fv = extract(lambda x: x * 2, jax.ShapeDtypeStruct((8,), jnp.float32),
                 launch=LaunchConfig(work_items=8))
    ds.add("app", "k", "s", fv, {"tpu-v5e": {"time_us": 12.5, "power_w": 80.0}})
    path = tmp_path / "ds.json"
    ds.save(path)
    ds2 = Dataset.load(path)
    X, y, _ = ds2.matrix("tpu-v5e", "time_us")
    assert X.shape == (1, len(FEATURE_NAMES))
    assert y[0] == 12.5


def test_overrepresentation_threshold():
    ds = Dataset()
    fv = extract(lambda x: x + 1, jax.ShapeDtypeStruct((4,), jnp.float32))
    for i in range(250):
        ds.add("app", "k", f"v{i}", fv, {"d": {"time_us": float(i)}})
    red = ds.reduce_overrepresented(max_per_group=100)
    assert len(red) == 100                      # paper §4.2.3


# ---------------------------------------------------------------- scheduler

def _fake_predictor(scale):
    def fn(X):
        return np.log(np.maximum(X[:, 3], 1.0) / scale + 15.0)
    return fn


def test_scheduler_prefers_fast_device():
    rng = np.random.default_rng(0)
    X = np.zeros((20, len(FEATURE_NAMES)))
    X[:, 3] = rng.uniform(1e6, 1e9, size=20)   # arith_ops
    devs = [DevicePredictor("fast", _fake_predictor(1e7), count=2),
            DevicePredictor("slow", _fake_predictor(1e5), count=2)]
    sched = schedule(X, devs)
    fast_share = np.mean([a.device == "fast" for a in sched.assignments])
    assert fast_share > 0.6
    assert sched.makespan_us > 0


def test_scheduler_beats_baselines():
    rng = np.random.default_rng(1)
    X = np.zeros((40, len(FEATURE_NAMES)))
    X[:, 3] = rng.uniform(1e6, 1e10, size=40)
    devs = [DevicePredictor("fast", _fake_predictor(1e7), count=2),
            DevicePredictor("slow", _fake_predictor(1e5), count=6)]
    out = speedup_vs_baseline(X, devs)
    assert out["speedup_vs_rr"] > 1.0
    assert out["predict_seconds"] < 1.0        # paper §7.1 latency budget
