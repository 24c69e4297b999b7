"""The server process of a run: the one process that holds the chip.

Started by ``run.py`` with the configuration's file; speaks to it in lines,
on its own stdin and a private copy of its stdout (everything else that
writes to stdout lands on stderr):

  -> DEVICE {platform, kind, count}      as soon as JAX has its devices
  <- FOREST <n> + n bytes                 a forest the load process fitted,
                                          as ``Forest.save`` writes it: one,
                                          or one per entry of the
                                          configuration's ``forests``, in
                                          their order
  -> READY {port, ...}                    server up, every shape warm
  <- TRACE_START / -> OK                  profiler on, window opened,
                                          counters snapshot
  <- TRACE_STOP / -> OK                   counters snapshot, window closed,
                                          profiler off
  <- END / -> RESULT {...}                final counters, peak memory, the
                                          reduced trace; then shut down

It serves ``ForestEngine`` (the configuration's backend) -> ``ReplicaPool``
-> ``ClusterFrontend`` -> ``PredictionServer`` on loopback, and exits when
its stdin closes. A configuration with ``forests`` gets one
``ForestEngine`` per entry, all of them behind one ``MultiDeviceEngine``
(log times, each device's DVFS grid from the ``targets`` file) that the
frontend schedules over (``devices=``), and the first time engine as the
pool's one member, which answers ``predict``. Every engine is warmed at
every size in ``--warm`` before READY, the pool's member also at the
sizes of the pool's health probes.
"""
from __future__ import annotations

import argparse
import dataclasses
import io
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from perfbench import xplane  # noqa: E402


#: Rows of the pool's health probes: 4, fewer where some are still cached.
PROBES = (1, 2, 4)


class NoChip(RuntimeError):
    pass


def require_chip(devices, chips: int) -> None:
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")


class Annotated:
    """The engine as the pool sees it, each call named in the profiler's
    trace with the rows it was given (traced runs only)."""

    def __init__(self, engine, jax_profiler):
        self.engine = engine
        self.n_features = engine.n_features
        self._profiler = jax_profiler

    def predict(self, X):
        with self._profiler.TraceAnnotation(xplane.CALL, rows=len(X)):
            return self.engine.predict(X)

    def close(self):
        self.engine.close()


def counters(frontend, engines: dict, pool) -> dict:
    """Snapshots of the counters: ``engine`` is the field-wise sum over
    every engine (for one engine, its own), ``engines`` each one's, by the
    column its forest was fitted on."""
    fs = dataclasses.asdict(frontend.stats_snapshot())
    fs.pop("by_replica"), fs.pop("by_tenant")
    each = {name: dataclasses.asdict(e.stats_snapshot())
            for name, e in engines.items()}
    first = next(iter(each.values()))
    return {"frontend": fs,
            "engine": {k: sum(snap[k] for snap in each.values())
                       for k in first},
            "engines": each,
            "pool": dataclasses.asdict(pool.stats_snapshot()),
            "latency": frontend.latency_summary(),
            "t": time.monotonic()}


def build_estimator(forest, max_depth):
    from repro.core.forest import ExtraTreesRegressor, Tree
    est = ExtraTreesRegressor(n_estimators=forest.n_trees,
                              max_depth=max_depth)
    est.trees_ = [Tree(**forest.tree(t)) for t in range(forest.n_trees)]
    est.n_features_ = forest.n_features
    return est


def main(argv=None, *, require_tpu: bool = True) -> int:
    proto = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)

    def say(word, doc=None):
        proto.write(word + ("" if doc is None else " " + json.dumps(doc))
                    + "\n")

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--warm", required=True,
                    help="comma-separated batch sizes the traffic reaches, "
                         "compiled before READY")
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--traced", type=int, default=0)
    args = ap.parse_args(argv)
    cfg = json.loads(Path(args.config).read_text())

    import jax
    devices = jax.devices()
    if require_tpu:
        try:
            require_chip(devices, args.chips)
        except NoChip as exc:
            print(f"server: {exc}", file=sys.stderr)
            return 1
    say("DEVICE", {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)})

    import numpy as np

    from perfbench.forest import Forest
    from repro.cluster import ClusterFrontend, PredictionServer, ReplicaPool
    from repro.serve import ForestEngine, MultiDeviceEngine

    entries = cfg.get("forests")
    names = [e["column"] for e in entries] if entries else ["y"]
    stdin = sys.stdin.buffer
    t0 = None
    engines = {}
    for name in names:
        line = stdin.readline().split()
        if not line or line[0] != b"FOREST":
            return 1
        t0 = t0 or time.monotonic()
        forest = Forest.load(io.BytesIO(stdin.read(int(line[1]))))
        engines[name] = ForestEngine(build_estimator(forest, cfg["max_depth"]),
                                     backend=cfg["backend"])
    # the pool's member: the first time forest, or the one forest
    first = next((e["column"] for e in entries or ()
                  if e["target"] == "time_us"), names[0])
    engine = engines[first]
    sizes = sorted({int(b) for b in args.warm.split(",")})
    most = max(*sizes, *PROBES)
    warm_rows = np.random.default_rng(0).lognormal(
        1.0, 1.5, size=(most, forest.n_features)).astype(np.float32)
    for name, e in engines.items():
        fn = e.predictor
        for b in sorted({*sizes, *(PROBES if name == first else ())}):
            np.asarray(fn(warm_rows[:b]))
    warm_s = time.monotonic() - t0

    tracing = Path(args.trace_dir)
    # the annotation costs a context manager per dispatch: only a traced
    # run pays it
    served = {name: Annotated(e, jax.profiler) if args.traced else e
              for name, e in engines.items()}
    pricing = None
    if entries:
        grids = json.loads((HERE.parent / cfg["targets"]).read_text())
        per = {}
        for e in entries:
            per.setdefault(e["device"], {})[e["target"]] = served[e["column"]]
        pricing = MultiDeviceEngine(
            per, log_time=True,
            freq_grids={d: tuple(grids["devices"][d]["freq_grid"])
                        for d in per})
    pool = ReplicaPool({"chip": served[first]})
    frontend = ClusterFrontend(pool, max_queue=cfg["frontend"]["max_queue"],
                               devices=pricing, auto_start=False)
    server = PredictionServer(frontend, port=0).start()
    say("READY", {"port": server.address[1], "warm_s": warm_s,
                  "backend": engine.backend})

    marks = {}
    try:
        for line in stdin:
            cmd = line.decode().strip()
            if cmd == "TRACE_START":
                shutil.rmtree(tracing, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(tracing), profiler_options=opts)
                window = jax.profiler.TraceAnnotation(xplane.WINDOW)
                window.__enter__()
                marks["start"] = counters(frontend, engines, pool)
                say("OK")
            elif cmd == "TRACE_STOP":
                marks["stop"] = counters(frontend, engines, pool)
                window.__exit__(None, None, None)
                jax.profiler.stop_trace()
                say("OK")
            elif cmd == "END":
                break
        stats = devices[0].memory_stats() or {}
        doc = {"final": counters(frontend, engines, pool), "marks": marks,
               "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
               "backend": engine.backend}
        if "stop" in marks:
            doc["trace"] = xplane.reduce_dir(tracing)
            shutil.rmtree(tracing, ignore_errors=True)
        say("RESULT", doc)
    finally:
        # PredictionServer.close() waits 5 s for its accept thread, which a
        # closed listener does not wake; its threads are daemons and end
        # with this process, so only the frontend, pool and engines (which
        # own the device work) are shut down here: the pool closes its
        # member, the others are closed after it
        frontend.close()
        for e in engines.values():
            e.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
