"""Forest-kernel scaling: the Pallas select formulation vs the gather-based
reference across batch sizes and depths. Off a TPU the kernel runs
interpreted, and its wall times are not TPU times: the deliverable there is
correctness at scale plus the structural select/VMEM accounting."""
from __future__ import annotations


import numpy as np

from repro.core.forest import ExtraTreesRegressor
from repro.core.forest_jax import DenseForestJax, to_dense
from repro.kernels.forest import PallasForest
from repro.kernels.forest.kernel import LANES, leaf_rows, level_offsets

from .common import StopWatch, dataset, emit, save_json


def run() -> dict:
    ds = dataset().reduce_overrepresented()
    X, y, _ = ds.matrix("tpu-v5e", "time_us")
    Xf = X.astype(np.float32)
    est = ExtraTreesRegressor(n_estimators=64, seed=0).fit(Xf, np.log(y))
    out = {}
    for depth in (8, 10):
        dense = to_dense(est, depth=depth)
        ref = DenseForestJax(dense)
        pf = PallasForest.from_dense(dense)
        rows = 2 * level_offsets(depth)[-1] + leaf_rows(depth)
        for B in (8, 64):
            xq = np.repeat(Xf, max(1, B // len(Xf) + 1), 0)[:B]
            r = np.asarray(ref(xq))
            with StopWatch() as sw:
                o = np.asarray(pf(xq))
            err = float(np.abs(o - r).max())
            # structural accounting: one select per (sample, tree lane,
            # table row) read, plus the double-buffered node tables in VMEM
            T = -(-dense.n_trees // LANES) * LANES
            selects = float(B * T * (rows + depth * Xf.shape[1]))
            vmem = 2 * rows * LANES * 4
            out[f"d{depth}_b{B}"] = {"max_err": err, "selects": selects,
                                     "vmem_bytes": vmem}
            emit(f"forest_kernel.d{depth}.b{B}", sw.seconds * 1e6,
                 f"max_err={err:.2e};selects={selects:.2e};"
                 f"vmem={vmem/2**20:.2f}MiB")
    save_json("forest_kernel", out)
    return out


if __name__ == "__main__":
    run()
