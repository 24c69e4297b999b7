"""Cold-start: serve a device the model has NEVER been trained on.

The paper's features are hardware-independent (§3.1), so they exist before
the first measurement on a new device — only the labels are missing. This
demo (docs/portability.md) stages the full SUPERVISED story:

 1. an `edge-dvfs` card shows up with NO spec sheet and NO training data;
    `build_transfer_engine` serves it IMMEDIATELY behind a ClusterFrontend
    (generic analytical prior),
 2. a `TransferSupervisor` closes the loop: probe measurements land in a
    DatasetStore and every `supervise_once` cycle feeds them back into the
    predictor AND the `calibration.mape` gauge — no operator code,
 3. the real spec sheet arrives MID-SERVE (`announce_spec`): the
    supervisor re-targets the prior and replays the store's full history
    onto it,
 4. the tier plateaus and the supervisor auto-graduates the device:
    `to_forest()` fitted off the serving locks, the `ForestEngine` swapped
    atomically into the live `ReplicaPool` slot (generation bump, zero
    dropped requests),
 5. a live StreamingCollector shows the `add_on_chunk(sup.on_chunk)`
    wiring that pokes the supervisor the instant new truth lands.

    PYTHONPATH=src python examples/coldstart_transfer.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

DEVICE = "edge-dvfs"


def main():
    from repro.cluster import ClusterFrontend, ReplicaPool
    from repro.core.dataset import DatasetStore, Sample
    from repro.core.devices import DEVICE_MODELS
    from repro.core.metrics import mape
    from repro.core.simulate import AnalyticalBaseline
    from repro.core.transfer import (TransferConfig, generic_device_prior,
                                     select_probes)
    from repro.obs.calibration import CalibrationMonitor
    from repro.obs.registry import MetricsRegistry
    from repro.serve import EngineConfig, build_transfer_engine
    from repro.serve.supervise import SupervisorConfig, TransferSupervisor
    from repro.workloads.collect import load_or_collect

    ds = load_or_collect(fast=True, progress=lambda *_: None)
    ds = ds.reduce_overrepresented()
    X, y, _ = ds.matrix(DEVICE, "time_us")
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(y))
    ev, pool_idx = perm[:60], perm[60:]
    Xev, yev, Xp, yp = X[ev], y[ev], X[pool_idx], y[pool_idx]

    print(f"== day zero: '{DEVICE}' arrives, spec sheet UNKNOWN ==")
    reg = MetricsRegistry()
    mon = CalibrationMonitor(reg, alpha=0.3)
    cold = build_transfer_engine(
        generic_device_prior(DEVICE), monitor=mon,
        config=TransferConfig(min_samples_leaf=4, shrinkage=32.0))
    store = DatasetStore()
    pool = ReplicaPool({"cold": cold})
    sup = TransferSupervisor(
        store, mon, pool=pool, registry=reg,
        config=SupervisorConfig(
            min_graduate_samples=48, plateau_window=3,
            engine_config=EngineConfig(backend="tree-walk", cache_size=0)))
    sup.manage(cold, replica="cold", key=DEVICE)
    fe = ClusterFrontend(pool)
    try:
        first = fe.predict(Xev[:4])
        print(f"   serving from second zero (mode={cold.mode}): "
              f"{np.array2string(first, precision=1)} us")

        am = AnalyticalBaseline(DEVICE_MODELS[DEVICE]).predict(Xev)
        am_mape = mape(yev, am)
        print(f"   static roofline that KNOWS the spec: {am_mape:5.1f}% MAPE"
              f" — the bar to clear\n")

        print("== supervised probe campaign (store -> supervisor -> "
              "model) ==")
        order = select_probes(Xp, len(Xp))

        def feed(idx, start):
            store.extend([Sample(app="demo", kernel=f"k{start + k}",
                                 variant="s", features=Xp[j],
                                 targets={DEVICE:
                                          {"time_us": float(yp[j])}})
                          for k, j in enumerate(idx)])
            return sup.supervise_once()

        seen = 0
        for n in (8, 16, 24):
            out = feed(order[seen:n], seen)
            seen = n
            m = mape(yev, fe.predict(Xev))
            print(f"   n={n:3d}  mode={cold.mode:6s}  ingested="
                  f"{out['ingested']}  eval MAPE {m:6.1f}%")

        print(f"\n== the real '{DEVICE}' spec sheet lands mid-serve ==")
        sup.announce_spec(DEVICE, DEVICE_MODELS[DEVICE])
        out = feed([], seen)
        st = cold.stats_snapshot()
        print(f"   re-targeted ({out['retargeted']}), store history "
              f"replayed: n_observed={st.n_observed}, clazz="
              f"{cold.device.clazz}")

        print("\n== stream on until the tier plateaus and auto-graduates ==")
        while seen < len(order):
            out = feed(order[seen:seen + 8], seen)
            seen += 8
            stage = sup.stats_snapshot()["devices"][DEVICE]["stage"]
            if out["graduated"]:
                print(f"   n={cold.stats_snapshot().n_observed:3d}  "
                      f"GRADUATED -> ForestEngine swapped into the live "
                      f"slot")
                break
            m = mape(yev, fe.predict(Xev))
            print(f"   n={seen:3d}  stage={stage:8s}  eval MAPE {m:6.1f}%")

        snap = sup.stats_snapshot()
        dev_state = snap["devices"][DEVICE]
        m_final = mape(yev, fe.predict(Xev))
        print(f"   slot generation {dev_state['slot_generation']}, "
              f"pool slot_swaps={pool.stats_snapshot().slot_swaps}, "
              f"graduated forest eval MAPE {m_final:6.1f}%")

        print("\n== post-graduation: same gauge keeps scoring the forest ==")
        out = feed(order[:4], 9000)       # four repeat measurements
        for row in reg.snapshot():
            if row["name"] == "calibration.mape":
                print(f"   live gauge calibration.mape{row['labels']} "
                      f"= {row['value']:.1f}%  "
                      f"(+{out['feedback']} feedback samples)")

        print("\n== live collector wiring (chunk -> wake the supervisor) ==")
        from repro.workloads.stream import StreamingCollector
        from repro.workloads.suite import suite

        coll = StreamingCollector(
            store, suite(sizes=("s",))[:4], repeats=2, measure=False,
            seed=11, chunk_size=4)
        coll.add_on_chunk(sup.on_chunk)   # poke, don't poll
        with sup:                         # background supervision loop
            coll.run_sync()
            sup.stop()
        s = snap["stats"]
        print(f"   supervisor totals: ingested={s.ingested} "
              f"retargets={s.retargets} graduations={s.graduations} "
              f"alerts={s.alerts}")
        print(f"\ncold-start summary: spec-aware static {am_mape:.1f}% vs "
              f"supervised lifecycle {m_final:.1f}% — prior -> fitted -> "
              f"hybrid -> forest with no operator in the loop")
    finally:
        fe.close()


if __name__ == "__main__":
    main()
