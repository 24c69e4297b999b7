"""Trace replay end to end: record a mixed-tenant trace, replay it against
an in-process cluster frontend, and print the per-tenant outcome report.

    PYTHONPATH=src python examples/trace_replay.py [--wire]

The script generates a 10-second mixed-tenant trace (an interactive tenant
with tight deadlines, a batch tenant with none, a best-effort tenant pinned
to a low priority), serializes it to the CRC-tagged JSONL format, reloads
it — the round trip is the point: what gets replayed is the ARTIFACT, not
in-memory state — and drives a demo frontend at recorded timestamps with
open-loop pacing. With ``--wire`` the same frontend is also put behind a
``PredictionServer`` on loopback TCP and the trace is replayed a second
time through a ``RemoteReplica``, showing that the replayer drives both
target shapes unchanged. Both replays run in this one process, which is
the only one that touches the device.

The final lines print each tenant's served/shed/expired counts, observed
wall-clock percentiles, and the deterministic outcome digest — the same
digest the golden-trace regression test pins across interpreters.
"""
import argparse
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.cluster.remote import demo_frontend  # noqa: E402
from repro.workloads.trace import (TraceReplayer, dump_trace,  # noqa: E402
                                   gen_tenant_mix, load_trace,
                                   synthetic_catalog)

N_FEATURES = 12


def record_trace(path: Path):
    ids, X = synthetic_catalog(32, N_FEATURES, seed=5)
    trace = gen_tenant_mix(
        ids, X, duration_s=10.0, seed=17,
        tenants={
            "interactive": {"rate": 25.0, "deadline_band": (0.3, 1.5)},
            "batch": {"rate": 15.0, "deadline_band": None},
            "best-effort": {"rate": 10.0, "deadline_band": (2.0, 6.0),
                            "priority": 9},
        })
    dump_trace(trace, path)
    print(f"recorded {len(trace)} events / {trace.duration_s():.1f}s "
          f"/ {len(trace.tenants())} tenants -> {path}")
    return path


def print_report(label: str, rep) -> None:
    print(f"\n[{label}] pacing={rep.pacing} speed={rep.speed:g} "
          f"wall={rep.wall_s:.2f}s digest={rep.digest()[:16]}")
    print(f"  {'tenant':<14}{'submitted':>10}{'served':>8}{'shed':>6}"
          f"{'expired':>8}{'retries':>8}{'p50 ms':>9}{'p99 ms':>9}")
    for tenant, s in sorted(rep.per_tenant.items()):
        print(f"  {tenant:<14}{s.submitted:>10}{s.served:>8}{s.shed:>6}"
              f"{s.expired:>8}{s.retries:>8}"
              f"{s.wall_percentile_ms(50):>9.2f}"
              f"{s.wall_percentile_ms(99):>9.2f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--wire", action="store_true",
                    help="also replay over loopback TCP through a "
                         "PredictionServer in this process")
    ap.add_argument("--speed", type=float, default=4.0,
                    help="replay speedup over recorded time (default 4x)")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as tmp:
        trace = load_trace(record_trace(Path(tmp) / "demo.jsonl"))

    fe = demo_frontend(seed=3, n_features=N_FEATURES).start()
    try:
        rep = TraceReplayer(fe, pacing="open", speed=args.speed).replay(trace)
        print_report("in-process frontend", rep)
        if args.wire:
            from repro.cluster import PredictionServer, RemoteReplica

            with PredictionServer(fe, port=0) as server, \
                    RemoteReplica(server.address, timeout_s=30.0) as replica:
                rep = TraceReplayer(replica, pacing="open",
                                    speed=args.speed).replay(trace)
            print_report("over the loopback wire", rep)
    finally:
        fe.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
