"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows (see per-module docstrings for
the paper table/figure each one reproduces), writes JSON artifacts under
artifacts/, and consolidates every emitted row into ``BENCH_results.json``
at the repo root (name -> us_per_call/derived) so the perf trajectory is
machine-readable across PRs. Profile via REPRO_BENCH_PROFILE={fast,paper}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

RESULTS_PATH = Path(__file__).resolve().parents[1] / "BENCH_results.json"

BENCHES = [
    ("dataset", "paper Fig 2/3/4 + s4.2.3", "benchmarks.bench_dataset"),
    ("cv", "paper Fig 5 (nested CV, primary device)", "benchmarks.bench_cv"),
    ("loo", "paper Fig 6/7 (leave-one-out)", "benchmarks.bench_loo"),
    ("portability", "paper Fig 8/9 + s8 summary", "benchmarks.bench_portability"),
    ("latency", "paper Tables 4/5 (+ beyond-paper paths)", "benchmarks.bench_latency"),
    ("importance", "paper Table 6", "benchmarks.bench_importance"),
    ("baseline", "paper s7.2 AM/LR comparison", "benchmarks.bench_analytical_baseline"),
    ("scheduler", "paper s1 use case quantified", "benchmarks.bench_scheduler"),
    ("trace", "workload diversity + trace codec (beyond-paper)", "benchmarks.bench_trace"),
    ("forest_kernel", "Pallas forest kernel checks", "benchmarks.bench_forest_kernel"),
    ("roofline", "SRoofline table from dry-run artifacts", "benchmarks.bench_roofline"),
]


def write_results(ran: list[str], failures: list[str]) -> None:
    """Consolidated machine-readable results at the repo root. Rows are
    keyed by emit() name (duplicates keep the LAST emit); reruns with
    ``--only`` merge into the existing file instead of clobbering other
    benches' rows. ``last_run`` describes THIS invocation only — rows not
    refreshed by it keep their recorded ``profile`` tag, and per-bench
    pass/fail state lives in the ``bench.<name>.wall`` rows themselves."""
    from . import common

    rows: dict = {}
    if RESULTS_PATH.exists():
        try:
            with open(RESULTS_PATH) as f:
                rows = json.load(f).get("rows", {})
        except (OSError, ValueError):
            pass
    for row in common.RESULTS:
        rows[row["name"]] = {"us_per_call": row["us_per_call"],
                             "derived": row["derived"],
                             "profile": common.PROFILE}
    payload = {"rows": rows,
               "last_run": {"profile": common.PROFILE, "ran": ran,
                            "failures": failures}}
    with open(RESULTS_PATH, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"# consolidated {len(common.RESULTS)} rows -> {RESULTS_PATH}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.core.platform import enable_compile_cache

    from .common import emit

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures, ran = [], []
    for name, what, module in BENCHES:
        if only and name not in only:
            continue
        ran.append(name)
        t0 = time.perf_counter()
        try:
            mod = __import__(module, fromlist=["run"])
            mod.run()
            emit(f"bench.{name}.wall", (time.perf_counter() - t0) * 1e6,
                 f"ok;{what}")
        except Exception as e:
            traceback.print_exc()
            failures.append(name)
            emit(f"bench.{name}.wall", (time.perf_counter() - t0) * 1e6,
                 f"FAILED:{type(e).__name__}")
    write_results(ran, failures)
    if failures:
        sys.exit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
