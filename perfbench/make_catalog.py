"""Write ``data/suite_catalog.json``, the training catalog of the forests,
and ``data/suite_targets.json``, the targets of every simulated device.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 perfbench/make_catalog.py

The catalog is the repository's workload suite (82 kernels at sizes s-xl,
328 rows) with its 12 hardware-independent features, lowered on the CPU,
and the log of the simulated ``tpu-v5e`` time (``collect`` at seed 0) as
the target ``y``. The targets file holds, over the same rows in the same
order, one column per simulated device and target: ``<device>/time_us``
(the log of the time in microseconds) and ``<device>/power_w`` (watts),
and each device's DVFS grid (``DeviceModel.freq_grid``). A configuration
names the columns its forests are fitted on (key ``forests``). Both files
are committed, so every run of the benchmark fits its forests on the same
rows whatever the program's suite later becomes; the benchmark itself
never runs this script.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
OUT = DATA / "suite_catalog.json"
TARGETS = DATA / "suite_targets.json"


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np

    from repro.core.devices import SIMULATED_DEVICES
    from repro.core.features import FEATURE_NAMES
    from repro.workloads.collect import collect
    from repro.workloads.suite import suite

    ds = collect(suite(sizes=("s", "m", "l", "xl"), seed=0), repeats=10,
                 measure=set(), seed=0)
    X, y, kept = ds.matrix("tpu-v5e", "time_us")
    X = X.astype(np.float32)
    names = [f"{s.app}/{s.kernel}/{s.variant}" for s in kept]
    doc = {
        "source": "repro.workloads.suite sizes s-xl, features lowered on "
                  "the CPU; target log time_us of the simulated tpu-v5e, "
                  "collect(seed=0)",
        "features": list(FEATURE_NAMES),
        "rows": names,
        "X": [[float(v) for v in row] for row in X],
        "y": [float(v) for v in np.log(y)],
    }
    OUT.write_text(json.dumps(doc, separators=(",", ":")) + "\n")

    columns, devices = {}, {}
    for dev in SIMULATED_DEVICES:
        for target, scale in (("time_us", np.log), ("power_w", np.asarray)):
            Xd, yd, kd = ds.matrix(dev.name, target)
            if [f"{s.app}/{s.kernel}/{s.variant}" for s in kd] != names \
                    or not np.array_equal(Xd.astype(np.float32), X):
                raise SystemExit(f"{dev.name}/{target}: not the catalog's "
                                 f"rows")
            columns[f"{dev.name}/{target}"] = [float(v) for v in scale(yd)]
        devices[dev.name] = {"freq_grid": list(dev.freq_grid)}
    targets = {
        "source": "repro.workloads.collect(seed=0) over the catalog's rows "
                  "(suite_catalog.json, same order): per simulated device "
                  "the log of time_us and power_w in watts; freq_grid from "
                  "repro.core.devices",
        "rows": names,
        "devices": devices,
        "columns": columns,
    }
    TARGETS.write_text(json.dumps(targets, separators=(",", ":")) + "\n")
    print(f"{OUT}: {X.shape[0]} rows x {X.shape[1]} features; {TARGETS}: "
          f"{len(columns)} columns")
    return 0


if __name__ == "__main__":
    sys.exit(main())
