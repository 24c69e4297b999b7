"""Find the knee of an open-loop mix once, on the chip: the highest rate
at which its latency stays steady and no backlog grows.

    python3 perfbench/sweep.py --workload et512-deep.single --seed 7 \
        --seconds 5 --rates 500,1000,2000,4000

One server serves one seed's forest; each rate gets a window of its own.
Per rate it prints a JSON line: p50 and p95 (ms), the median latency of the
last fifth of the requests over that of the first fifth (about 1 when no
backlog grows), how late the generator ran (99th percentile and most),
and requests not answered. An open-loop cell runs at about four fifths of
its mix's highest steady rate, where its tails are end-to-end metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import run, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    args.trace = 0
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.load_cell(bench, args.workload)
    s = run.start(args, cell)
    try:
        print(json.dumps({"setup_s": s.setup_s, **s.ready}), flush=True)
        drawer = traffic.Drawer(cell.mix, s.catalog, args.seed)
        for rate in map(float, args.rates.split(",")):
            mix = dict(cell.mix, rate_per_s=rate)
            sent, _, late = run.open_loop(s.clients, mix, drawer, args.seed,
                                          args.seconds)
            lat = np.array([x.t1 - x.t0 if x.error is None else np.inf
                            for x in sent])
            fifth = max(len(lat) // 5, 1)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(lat),
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p95_ms": float(np.percentile(lat, 95) * 1e3),
                "growth": float(np.median(lat[-fifth:])
                                / np.median(lat[:fifth])),
                "generator_p99_ms": late["p99_ms"],
                "generator_max_ms": late["max_ms"],
                "unanswered": int(sum(x.error is not None for x in sent))}),
                flush=True)
        run.finish(s)
    finally:
        s.server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
