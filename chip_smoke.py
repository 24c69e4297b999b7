"""End-to-end smoke run of the main path on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4           # the tree-sharded engine only

Phases, in one process (a chip belongs to one process):

  1. device  — require a TPU; print its kind, count and the compile cache.
  2. data    — features of the whole workload suite (82 kernels, sizes
               s-xl) lowered from its HLO, and 16 seeded kernels timed on
               the chip, filed under the chip's ``device_kind``.
  3. fit     — the paper's Table 4/5 deployment: 512 extremely randomized
               trees of unbounded depth on the simulated ``tpu-v5e`` time
               target, in log space.
  4. serve   — that forest through ``flat-jax`` on the chip behind
               ReplicaPool -> ClusterFrontend -> PredictionServer on
               loopback, queried by a RemoteReplica; every answer must
               match the tree-walk within 1e-5 relative.
  5. pallas  — a 512-tree depth-10 forest served by the compiled Pallas
               kernel, checked the same way.

With ``--chips 4`` only the sharded engine runs: the depth-10 forest over
4 devices in the mesh and the loop placements, each shard on its own chip,
against the tree-walk. Any failure raises and exits non-zero; the last line
of a passing run is one JSON object naming the device. Wall times printed
here include compilation and are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster import (ClusterFrontend, PredictionServer,  # noqa: E402
                           RemoteReplica, ReplicaPool)
from repro.core.forest import ExtraTreesRegressor  # noqa: E402
from repro.core.platform import enable_compile_cache  # noqa: E402
from repro.serve import ForestEngine, ShardedForestEngine  # noqa: E402
from repro.workloads.collect import collect, measured_device  # noqa: E402
from repro.workloads.suite import suite  # noqa: E402

TREES = 512
TIMED_KERNELS = 16
RTOL = 1e-5


def max_rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.all(np.isfinite(got)), "non-finite prediction"
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-9)))


def check(label: str, got, want) -> float:
    err = max_rel_err(got, want)
    if err > RTOL:
        raise AssertionError(f"{label}: max rel err {err:.3e} > {RTOL:g}")
    return err


def phase_device(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devices)} device(s)")
    cache = enable_compile_cache()
    print(f"[device] kind={devices[0].device_kind} count={len(devices)} "
          f"compile_cache={cache}", flush=True)
    return devices


def phase_data(seed: int, timed_kernels: int):
    """Suite features from a seed, with ``timed_kernels`` kernels (one
    seeded size each) timed on the chip."""
    workloads = suite(sizes=("s", "m", "l", "xl"), seed=seed)
    kernels = sorted({(w.app, w.kernel) for w in workloads})
    rng = np.random.default_rng(seed)
    picked = {kernels[i] for i in rng.choice(len(kernels), timed_kernels,
                                              replace=False)}
    by_kernel: dict = {}
    for i, w in enumerate(workloads):
        by_kernel.setdefault((w.app, w.kernel), []).append(i)
    timed = {int(rng.choice(by_kernel[k])) for k in sorted(picked)}
    t0 = time.perf_counter()
    ds = collect(workloads, repeats=10, measure=timed, seed=seed)
    label = measured_device()
    if timed:
        assert label == jax.devices()[0].device_kind, label
    for i, s in enumerate(ds.samples):
        assert (label in s.targets) == (i in timed), (i, s.targets.keys())
        if i in timed:
            t = s.targets[label]
            assert np.isfinite(t["time_us"]) and t["time_us"] > 0, t
            print(f"[data] {s.app}/{s.kernel}/{s.variant} on {label}: "
                  f"median {t['time_us']:.1f} us, CoV {t['time_cov']:.3f}",
                  flush=True)
    X, y, _ = ds.matrix("tpu-v5e", "time_us")
    assert X.shape == (len(workloads), 12) and np.all(np.isfinite(X)), X.shape
    print(f"[data] {len(workloads)} workloads over {len(kernels)} kernels "
          f"({X.shape[1]} features each), {len(timed)} timed on {label!r}, "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return X.astype(np.float32), np.log(y)


def fit(X, y, seed: int, max_depth: int | None) -> ExtraTreesRegressor:
    est = ExtraTreesRegressor(n_estimators=TREES, max_depth=max_depth,
                              seed=seed).fit(X, y)
    depths = [t.depth() for t in est.trees_]
    print(f"[fit] {TREES} trees, max_depth={max_depth}: depth max "
          f"{max(depths)} mean {np.mean(depths):.1f}, "
          f"{sum(t.n_nodes for t in est.trees_)} nodes", flush=True)
    return est


def on_chip(arrays, chip) -> None:
    where = {d for a in arrays for d in a.devices()}
    assert where == {chip}, f"backend arrays on {where}, not {chip}"


def phase_serve(est, X, chip) -> None:
    engine = ForestEngine(est, backend="flat-jax", cache_size=0)
    on_chip(engine.predictor.__wrapped__.arrays, chip)
    frontend = ClusterFrontend(ReplicaPool({"chip": engine}),
                               max_queue=4 * len(X), auto_start=False)
    with PredictionServer(frontend, port=0) as server, \
            RemoteReplica(server.address, timeout_s=300.0) as client:
        for rows in (X[:1], X[:64], X):
            t0 = time.perf_counter()
            got = client.predict(rows)
            wall = time.perf_counter() - t0
            err = check(f"flat-jax over the wire, {len(rows)} rows",
                        got, est.predict(rows))
            print(f"[serve] flat-jax over the wire, {len(rows)} rows: max "
                  f"rel err {err:.2e}, wall {wall * 1e3:.1f} ms (includes "
                  f"compilation; not a benchmark number)", flush=True)


def phase_pallas(est, X, chip) -> None:
    with ForestEngine(est, backend="pallas", cache_size=0) as engine:
        kernel = engine.predictor.__wrapped__
        on_chip(kernel.tables, chip)
        assert kernel.static["interpret"] is False, kernel.static
        lowered = kernel.lower(X).as_text()
        assert "tpu_custom_call" in lowered, "Pallas kernel not in program"
        for rows in (X[:1], X):
            t0 = time.perf_counter()
            got = engine.predict(rows)
            wall = time.perf_counter() - t0
            err = check(f"pallas, {len(rows)} rows", got, est.predict(rows))
            print(f"[pallas] compiled kernel (tpu_custom_call), depth "
                  f"{kernel.static['depth']}, {len(rows)} rows: max rel err "
                  f"{err:.2e}, wall {wall * 1e3:.1f} ms (includes "
                  f"compilation; not a benchmark number)", flush=True)


def phase_sharded(est, X, n_chips: int) -> None:
    want = est.predict(X)
    for force_loop in (False, True):
        with ShardedForestEngine(est, n_shards=n_chips, force_loop=force_loop,
                                 cache_size=0) as engine:
            devices = engine.shard_devices
            assert len(set(devices)) == n_chips, (engine.placement, devices)
            err = check(f"sharded {engine.placement}", engine.predict(X),
                        want)
            print(f"[sharded] {engine.backend}: {n_chips} shards on devices "
                  f"{sorted(d.id for d in devices)}, max rel err {err:.2e}",
                  flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tree-sharded engine over 4 chips")
    args = ap.parse_args(argv)

    devices = phase_device(args.chips)
    if args.chips == 4:
        X, y = phase_data(args.seed, timed_kernels=0)
        phase_sharded(fit(X, y, args.seed, max_depth=10), X, args.chips)
    else:
        X, y = phase_data(args.seed, timed_kernels=TIMED_KERNELS)
        phase_serve(fit(X, y, args.seed, max_depth=None), X, devices[0])
        phase_pallas(fit(X, y, args.seed, max_depth=10), X, devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
