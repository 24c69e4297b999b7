"""Write ``data/suite_catalog.json``: the training catalog of both forests.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 perfbench/make_catalog.py

The catalog is the repository's workload suite (82 kernels at sizes s-xl,
328 rows) with its 12 hardware-independent features, lowered on the CPU,
and the log of the simulated ``tpu-v5e`` time (``collect`` at seed 0) as
the target. It is committed, so every run of the benchmark fits its forests
on the same rows whatever the program's suite later becomes; the benchmark
itself never runs this script.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT = Path(__file__).resolve().parent / "data" / "suite_catalog.json"


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np

    from repro.core.features import FEATURE_NAMES
    from repro.workloads.collect import collect
    from repro.workloads.suite import suite

    ds = collect(suite(sizes=("s", "m", "l", "xl"), seed=0), repeats=10,
                 measure=set(), seed=0)
    X, y, kept = ds.matrix("tpu-v5e", "time_us")
    X = X.astype(np.float32)
    doc = {
        "source": "repro.workloads.suite sizes s-xl, features lowered on "
                  "the CPU; target log time_us of the simulated tpu-v5e, "
                  "collect(seed=0)",
        "features": list(FEATURE_NAMES),
        "rows": [f"{s.app}/{s.kernel}/{s.variant}" for s in kept],
        "X": [[float(v) for v in row] for row in X],
        "y": [float(v) for v in np.log(y)],
    }
    OUT.write_text(json.dumps(doc, separators=(",", ":")) + "\n")
    print(f"{OUT}: {X.shape[0]} rows x {X.shape[1]} features")
    return 0


if __name__ == "__main__":
    sys.exit(main())
