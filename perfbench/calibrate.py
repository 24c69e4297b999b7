"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/calibrate.py --workload et512-deep.batch \
        --seeds 11,12,13 --seconds 3

For each seed, one whole run of the cell at its own load (a short window)
gives the program's readings, the numbers its answers were checked by;
the control, the reference walked in bfloat16 (``forest.walk_bf16``), is
read on the same rows against the same reference: for ``predict`` its
answers' ``max_rel_err``, for ``schedule`` every number of
``run.compare_schedule`` for the schedules that ``schedule.py`` makes from
the bfloat16 walks of every forest. Prints one JSON line per seed. The
benchmark's own runs never run this.
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
from perfbench import schedule as ref_schedule  # noqa: E402


def predict_control(out: dict) -> dict:
    chk = out["check"]
    (forest,) = out["forests"].values()
    control = pf.walk_bf16(forest, chk["X"])
    scale = np.maximum(np.abs(chk["want"]), 1.0)
    return {"max_rel_err": float(np.max(np.abs(control - chk["want"])
                                        / scale))}


def schedule_control(out: dict, cell: run.Cell) -> dict:
    chk, forests = out["check"], out["forests"]
    devices = run.Devices.of(cell.config)
    t = np.exp(np.stack([pf.walk_bf16(forests[c], chk["X"])
                         for c in devices.time], axis=1))
    p = np.stack([pf.walk_bf16(forests[c], chk["X"])
                  for c in devices.power], axis=1)
    worst: dict = {}
    cut = chk["cut"]
    for lo, hi in zip(cut[:-1], cut[1:]):
        ctl = ref_schedule.schedule(t[lo:hi], p[lo:hi], devices.grids,
                                    cell.mix["objective"],
                                    run.deadline_us(cell.mix))
        ctl["assignments"] = [dict(a, device=devices.names[a["device"]],
                                   queue_slot=0)
                              for a in ctl["assignments"]]
        read = run.compare_schedule(ctl, chk["t_nom"][lo:hi],
                                    chk["p_nom"][lo:hi], devices, cell.mix)
        for k, v in read.items():
            worst[k] = max(worst.get(k, v), v)
    return worst


def main(argv=None, *, bench=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = bench if bench is not None else json.loads(
        (run.ROOT / "BENCHMARK.json").read_text())
    cell = run.load_cell(bench, args.workload)
    for seed in map(int, args.seeds.split(",")):
        out = run.run(run.parse(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)]),
                      bench=bench)
        control = (schedule_control(out, cell)
                   if cell.mix.get("op", "predict") == "schedule"
                   else predict_control(out))
        print(json.dumps({
            "seed": seed, "correct": out["line"]["correct"],
            "program": {k: c["value"]
                        for k, c in out["line"]["checks"].items()},
            "control": control, "rows_checked": out["check"]["rows"],
            "attempted": out["line"]["attempted"],
            "backend": out["info"]["backend"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
