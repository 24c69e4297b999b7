"""Readings that the limit of ``correct`` is set from, on the chip.

    python3 perfbench/calibrate.py --workload et512-deep.batch \
        --seeds 11,12,13 --seconds 3

For each seed, one whole run of the cell at its own load (a short window)
gives the program's reading, ``max_rel_err`` of the answers it checked;
the control, ``forest.walk_bf16`` (the walk in bfloat16), is read on the
same rows against the same reference. Prints one JSON line per seed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import forest as pf  # noqa: E402
from perfbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in map(int, args.seeds.split(",")):
        out = run.run(run.parse(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)]))
        chk = out["check"]
        control = pf.walk_bf16(out["forest"], chk["X"])
        scale = np.maximum(np.abs(chk["want"]), 1.0)
        print(json.dumps({
            "seed": seed, "correct": out["line"]["correct"],
            "program": out["line"]["checks"]["max_rel_err"]["value"],
            "control": float(np.max(np.abs(control - chk["want"]) / scale)),
            "rows_checked": chk["rows"],
            "attempted": out["line"]["attempted"],
            "backend": out["info"]["backend"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
