"""Prediction-latency measurement (paper §6.1/§6.2, Tables 4 & 5).

The paper measures 15–108 ms per single prediction on a Xeon E5-2667v3 and
argues (§7.1) this bounds the schedulers the model can serve. We measure the
same quantity for every inference path in this repo:

  * ``tree-walk``  : per-tree numpy traversal (the paper's deployment path)
  * ``flat-numpy`` : vectorized flattened-forest numpy
  * ``flat-jax``   : jit-compiled exact walk: per-element gathers, or selects
                     over level-packed tables on a TPU
  * ``dense-jax``  : complete-tree layout (the Pallas kernel's oracle)
  * ``pallas``     : the dense-layout Pallas kernel (interpreted off-TPU)

producing the paper-faithful baseline AND the beyond-paper hillclimb in one
table (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np


@dataclass
class LatencyResult:
    name: str
    single_ms: float          # one sample, one prediction (paper's metric)
    batch_us_per_sample: float
    batch_size: int

    def row(self) -> str:
        return (f"{self.name},{self.single_ms:.3f}ms/single,"
                f"{self.batch_us_per_sample:.2f}us/sample@B{self.batch_size}")


def _bench(fn, x_single, x_batch, warmup: int = 3, iters: int = 20) -> tuple[float, float]:
    for _ in range(warmup):
        fn(x_single)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(x_single)
    single_ms = (time.perf_counter() - t0) / iters * 1e3
    for _ in range(2):
        fn(x_batch)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(x_batch)
    batch_us = (time.perf_counter() - t0) / iters / x_batch.shape[0] * 1e6
    return single_ms, batch_us


def time_call(fn, x, warmup: int = 1, iters: int = 3) -> float:
    """Median-free quick timing: seconds per ``fn(x)`` call."""
    for _ in range(warmup):
        fn(x)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(x)
    return (time.perf_counter() - t0) / iters


def calibrate_backends(fns: dict, x_batch: np.ndarray,
                       warmup: int = 1, iters: int = 3) -> dict[str, float]:
    """Self-calibration pass for the serving engine: time every candidate
    inference path on one flush-sized batch (the engine's unit of work) and
    return {name: seconds}. A path that fails to run raises."""
    return {name: time_call(fn, x_batch, warmup=warmup, iters=iters)
            for name, fn in fns.items()}


def measure_paths(est, X: np.ndarray, batch: int = 256,
                  dense_depth: int = 10, include_pallas: bool = True,
                  ) -> list[LatencyResult]:
    from .forest import predict_flat
    from .forest_jax import DenseForestJax, FlatForestJax, to_dense

    rng = np.random.default_rng(0)
    x1 = X[:1]
    xb = X[rng.integers(0, X.shape[0], size=batch)]
    out: list[LatencyResult] = []

    def tree_walk(x):
        return est.predict(x)
    s, b = _bench(tree_walk, x1, xb)
    out.append(LatencyResult("tree-walk", s, b, batch))

    flat = est.to_flat()
    s, b = _bench(lambda x: predict_flat(flat, x), x1, xb)
    out.append(LatencyResult("flat-numpy", s, b, batch))

    fj = FlatForestJax(flat)
    s, b = _bench(lambda x: np.asarray(fj(x)), x1, xb)
    out.append(LatencyResult("flat-jax", s, b, batch))

    dense = to_dense(est, depth=dense_depth)
    dj = DenseForestJax(dense)
    s, b = _bench(lambda x: np.asarray(dj(x)), x1, xb)
    out.append(LatencyResult("dense-jax", s, b, batch))

    if include_pallas:
        from ..kernels.forest import PallasForest
        pf = PallasForest.from_dense(dense)
        s, b = _bench(lambda x: np.asarray(pf(x)), x1, xb)
        out.append(LatencyResult("pallas", s, b, batch))
    return out
