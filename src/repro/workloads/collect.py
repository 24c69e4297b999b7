"""Ground-truth collection over the workload suite (paper §4.2).

Per workload:
  * features from the lowered StableHLO (recorded ONCE — portability),
  * REAL wall-clock on JAX's default device, repeated ``repeats`` times,
    median kept, CoV recorded (paper Fig. 3). It is filed under
    ``measured_device()``: ``cpu-host`` on a CPU, the device's
    ``device_kind`` (e.g. ``TPU v5 lite``) anywhere else,
  * each simulated TPU device model: analytic time (median-of-10 noisy
    draws) + power (mean-of-10) — the SIMULATED GATE, DESIGN.md §6.

Returns a ``repro.core.dataset.Dataset``; cached as JSON under artifacts/.
"""
from __future__ import annotations

import time
from collections.abc import Collection
from pathlib import Path

import jax
import numpy as np

from ..core.dataset import Dataset
from ..core.devices import CPU_HOST, SIMULATED_DEVICES
from ..core.features import LaunchConfig, extract_from_lowered
from ..core.power import simulate_power_mean_w
from ..core.simulate import WorkloadSpec, simulate_time_median_us
from .suite import Workload, suite

ARTIFACT = Path(__file__).resolve().parents[3] / "artifacts" / "suite_dataset.json"


def measured_device() -> str:
    """The name real timings are filed under: follows the platform."""
    dev = jax.devices()[0]
    return CPU_HOST.name if dev.platform == "cpu" else dev.device_kind


def _measure(fn, args, repeats: int) -> tuple[float, float]:
    jitted = jax.jit(fn)
    out = jitted(*args)
    jax.block_until_ready(out)
    xs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        xs.append((time.perf_counter() - t0) * 1e6)
    xs = np.asarray(xs)
    return float(np.median(xs)), float(xs.std() / max(xs.mean(), 1e-9))


def spec_from_features(fv, work_items: float, n_shards: int = 1) -> WorkloadSpec:
    aux = fv.aux
    return WorkloadSpec(
        flops=max(aux["flops"], 1.0),
        hbm_bytes=max(aux["hbm_bytes"], 1.0),
        collective_bytes=aux["collective_bytes"],
        special_ops=aux["special_ops"],
        control_ops=aux["control_ops"],
        work_items=work_items,
        n_shards=n_shards)


def measure_workload(w: Workload, rng, repeats: int = 10,
                     measure: bool = True):
    """Features (extracted ONCE from the portable IR) + per-device targets
    for ONE workload. Shared by the batch collector below and the streaming
    collector (``workloads/stream.py``): given the same rng state it yields
    identical measurements on the simulated devices, which is what makes
    streamed and batch-collected datasets byte-identical under one seed.
    ``measure=False`` skips the wall-clock timing (features and simulated
    targets only). Returns (FeatureVector, targets dict)."""
    lowered = jax.jit(w.fn).lower(*w.args)
    fv = extract_from_lowered(lowered, LaunchConfig(work_items=w.work_items))
    targets = {}
    if measure:
        t_us, cov = _measure(w.fn, w.args, repeats)
        targets[measured_device()] = {"time_us": t_us, "time_cov": cov}
    spec = spec_from_features(fv, w.work_items)
    for dev in SIMULATED_DEVICES:
        t_us, tcov = simulate_time_median_us(spec, dev, rng, repeats)
        p_w, pcov = simulate_power_mean_w(spec, dev, rng, repeats)
        targets[dev.name] = {"time_us": t_us, "time_cov": tcov,
                             "power_w": p_w, "power_cov": pcov}
    return fv, targets


def collect(workloads: list[Workload] | None = None, repeats: int = 10,
            measure: bool | Collection[int] = True, seed: int = 0,
            progress=None) -> Dataset:
    """``measure``: time every workload (True), none (False), or only the
    workloads at these indices."""
    workloads = workloads if workloads is not None else suite()
    ds = Dataset()
    rng = np.random.default_rng(seed)
    for i, w in enumerate(workloads):
        timed = measure if isinstance(measure, bool) else i in measure
        fv, targets = measure_workload(w, rng, repeats, timed)
        ds.add(w.app, w.kernel, w.variant, fv, targets)
        if progress and (i + 1) % 20 == 0:
            progress(f"  collected {i+1}/{len(workloads)}")
    return ds


def cells_dataset(dryrun_dir: Path | None = None, seed: int = 1,
                  repeats: int = 10) -> Dataset:
    """The 40-cell dry-run programs as predictor samples: their portable
    features were extracted at lowering time (launch/dryrun.py); here we
    attach simulated per-device targets. These are the SECONDS-scale
    samples (train/prefill steps of 0.1B..123B models) that extend the
    dataset's dynamic range to the paper's ~8 orders of magnitude —
    and they make the predictor applicable to the framework's own
    scheduling (autotuner / straggler monitor)."""
    import json
    from ..core.features import FEATURE_NAMES, FeatureVector

    dryrun_dir = dryrun_dir or (
        Path(__file__).resolve().parents[3] / "artifacts" / "dryrun")
    rng = np.random.default_rng(seed)
    ds = Dataset()
    for p in sorted(dryrun_dir.glob("*.json")):
        with open(p) as f:
            rec = json.load(f)
        if rec.get("status") != "ok" or "features" not in rec:
            continue
        vals = np.asarray([rec["features"][n] for n in FEATURE_NAMES])
        fv = FeatureVector(values=vals, aux=rec["feature_aux"])
        arch, shape, mesh, strat = rec["tag"].split("__")
        spec = spec_from_features(fv, fv.aux["work_items"],
                                  n_shards=int(fv.aux["n_shards"]))
        targets = {}
        for dev in SIMULATED_DEVICES:
            t_us, tcov = simulate_time_median_us(spec, dev, rng, repeats)
            p_w, pcov = simulate_power_mean_w(spec, dev, rng, repeats)
            targets[dev.name] = {"time_us": t_us, "time_cov": tcov,
                                 "power_w": p_w, "power_cov": pcov}
        ds.add(f"framework-{arch}", shape, mesh, fv, targets)
    return ds


def load_or_collect(path: Path = ARTIFACT, fast: bool = False,
                    progress=print, include_cells: bool = True) -> Dataset:
    if path.exists():
        return Dataset.load(path)
    sizes = ("s", "m", "l") if fast else ("s", "m", "l", "xl")
    ds = collect(suite(sizes=sizes), repeats=5 if fast else 10,
                 progress=progress)
    if include_cells:
        ds.samples.extend(cells_dataset().samples)
    ds.save(path)
    return ds
