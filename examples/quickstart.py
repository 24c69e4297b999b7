"""Quickstart: the paper's full pipeline in one file.

 1. take a handful of JAX compute kernels (from the workload suite),
 2. extract hardware-independent features from their StableHLO (recorded
    once — the portability property),
 3. measure ground-truth wall time on THIS machine (cpu-host),
 4. train the Extremely Randomized Trees model,
 5. predict held-out kernels and report MAPE + single-prediction latency.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core.forest import ExtraTreesRegressor, predict_flat
from repro.core.metrics import mape
from repro.core.split import time_stratified_kfold
from repro.workloads.collect import collect
from repro.workloads.suite import suite


def main():
    print("collecting workloads (features once + CPU wall-clock)...")
    workloads = suite(sizes=("s", "m"))
    ds = collect(workloads, repeats=5, measure=True,
                 progress=lambda m: print(m))
    X, y, kept = ds.matrix("cpu-host", "time_us")
    print(f"dataset: {len(y)} kernels, {y.min():.0f}..{y.max():.0f} us")

    rng = np.random.default_rng(0)
    folds = time_stratified_kfold(y, 4, rng)
    scores = []
    for fold in folds:
        est = ExtraTreesRegressor(n_estimators=64, criterion="mse",
                                  max_features="max", seed=0)
        est.fit(X[fold.train].astype(np.float32), np.log(y[fold.train]))
        pred = np.exp(est.predict(X[fold.test].astype(np.float32)))
        scores.append(mape(y[fold.test], pred))
    print(f"4-fold time-prediction MAPE: median {np.median(scores):.1f}% "
          f"(paper K20: median 13.9%)")

    # prediction latency (paper Tables 4/5: 15-108 ms; our flat path: us)
    est = ExtraTreesRegressor(n_estimators=128, seed=0).fit(
        X.astype(np.float32), np.log(y))
    flat = est.to_flat()
    x1 = X[:1].astype(np.float32)
    predict_flat(flat, x1)
    t0 = time.perf_counter()
    for _ in range(50):
        predict_flat(flat, x1)
    lat = (time.perf_counter() - t0) / 50 * 1e3
    t0 = time.perf_counter()
    est.predict(x1)
    walk = (time.perf_counter() - t0) * 1e3
    print(f"single prediction: tree-walk {walk:.1f} ms (paper's path), "
          f"flat {lat:.3f} ms ({walk/lat:.0f}x)")


if __name__ == "__main__":
    main()
