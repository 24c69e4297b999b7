"""Streaming serving loop: collect -> snapshot -> refit -> hot-swap, live.

The one-shot flow (collect() -> fit() -> ForestEngine) cannot ingest new
ground truth. This demo runs the full streaming stack instead:

  StreamingCollector (background thread, measures workloads incrementally)
      └─> DatasetStore (versioned, deterministic over-representation cap)
            └─> EngineRefresher (background thread: refit on each snapshot,
                  atomically hot-swap into the LIVE engines)
                    └─> ForestEngine / ShardedForestEngine serving a
                          concurrent prediction stream the whole time

Every answered batch is generation-uniform even while swaps land mid-storm.

    PYTHONPATH=src python examples/streaming_serve.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np


def main():
    from repro.core.dataset import DatasetStore
    from repro.serve import (EngineRefresher, ForestEngine,
                             ShardedForestEngine, single_device_fit_fn)
    from repro.workloads.stream import StreamingCollector, iter_samples
    from repro.workloads.suite import suite

    device = "tpu-v5e"
    workloads = suite(sizes=("s",))
    store = DatasetStore(max_per_group=100, seed=0)

    print(f"== bootstrap: measure the first workloads ({device}) ==")
    bootstrap, rest = workloads[:24], workloads[24:]
    store.extend(list(iter_samples(bootstrap, repeats=3, measure=False,
                                   seed=0)))
    fit = single_device_fit_fn(device, n_estimators=32)
    snap = store.snapshot()
    eng = ForestEngine(fit(snap.dataset), backend="flat-numpy", max_batch=32)
    print(f"   store v{snap.version}: {len(snap.dataset)} samples, "
          f"serving generation {eng.generation}")

    print("== stream the rest while serving ==")
    X0, _, _ = snap.dataset.matrix(device, "time_us")
    X0 = X0.astype(np.float32)
    collector = StreamingCollector(store, rest, repeats=3, measure=False,
                                   seed=0, chunk_size=16)
    refresher = EngineRefresher(store, eng, fit, poll_s=0.02)
    served = 0
    deadline = time.monotonic() + 300           # bound the demo loop: a
    with collector, refresher:                  # blacklisted final refit
        while time.monotonic() < deadline:      # must not hang it
            caught_up = refresher.stats.last_version >= store.version
            gave_up = refresher.stats.failed_version == store.version
            if collector.done.is_set() and (caught_up or gave_up):
                break
            futs = [eng.predict_async(X0[i % X0.shape[0]])
                    for i in range(16)]
            for f in futs:
                f.result(timeout=30)
            served += len(futs)
            time.sleep(0.01)
            if served % 320 == 0:
                print(f"   served={served:5d}  store v{store.version} "
                      f"({len(store)} samples)  generation={eng.generation}  "
                      f"hit_rate={eng.stats.hit_rate():.2f}")
    print(f"   final: {len(store)} samples, store v{store.version}, "
          f"{refresher.stats.refreshes} refreshes, "
          f"engine generation {eng.generation}")
    s = eng.stats
    print(f"   engine: {s.requests} requests, {s.batches} forest calls, "
          f"hit_rate={s.hit_rate():.2f}, swaps={s.swaps}")
    eng.close()

    print("== same data, tree-axis partitioned (ShardedForestEngine) ==")
    from repro.core.forest import ExtraTreesRegressor
    Xs, ys, _ = store.snapshot().dataset.matrix(device, "time_us")
    # cap tree depth below the dense embedding depth so the partitioned
    # prediction is exact (deeper forests get the documented bounded
    # truncation of the dense layout)
    est = ExtraTreesRegressor(n_estimators=32, max_depth=8, seed=0).fit(
        Xs.astype(np.float32), np.log(ys))
    oracle = est.predict(X0[:16])
    with ShardedForestEngine(est, n_shards=2) as sh:
        pred = sh.predict(X0[:16])
        rel = np.max(np.abs(pred - oracle) / np.maximum(np.abs(oracle), 1e-9))
        print(f"   backend={sh.backend} placement={sh.placement} "
              f"shards={sh.shard_sizes} max_rel_err_vs_oracle={rel:.1e}")
        print("   (run under XLA_FLAGS=--xla_force_host_platform_device_count=4"
              " to see the shard_map mesh placement)")
    print("done.")


if __name__ == "__main__":
    main()
