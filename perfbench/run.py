"""One run of one benchmark cell: the load process.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``. This process fits the configuration's forest
from its ``fit_seed`` (``forest.fit``), starts ``server.py`` (the one
process that touches JAX's backend, and so the chip), hands it the forest,
and once the server is warm drives the mix through ``RemoteReplica``
clients over loopback for ``--seconds``, timing every request on the
client side. The seed draws the rows, their noise and their order.

With ``--trace 1`` the server also records the profiler's trace over the
window less ``TRACE_MARGIN_S`` at each end, and the line carries the
per-layer metrics; otherwise the end-to-end ones. After the window every
answer due is awaited (up to ``LATE_S``), the server is shut down, and the
answers are checked against the plain walk of the same forest
(``forest.walk``): every row that repeats a catalog row, and
``CHECK_ROWS`` fresh rows drawn from the seed. The last line of stdout is
the result, as JSON; the last lines of stderr are the numbers compared,
each with its limit.

A run that finds no TPU, or fewer chips than the cell asks for, exits 1
and prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import forest as pf  # noqa: E402
from perfbench import readings, traffic, work  # noqa: E402

CACHE = HERE / ".cache"
CATALOG = HERE / "data" / "suite_catalog.json"
CHECK_ROWS = 8192        # rows checked when the mix's rows never repeat
LATE_S = 60.0            # how long answers due in the window are awaited
TRACE_MARGIN_S = 1.0     # untraced start and end of a --trace 1 window
#: Largest relative gap |served - walk| / max(|walk|, 1) that passes.
MAX_REL_ERR = 1e-5


class Refused(RuntimeError):
    """The run cannot measure: no chip, or the server failed to start."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    config_file: Path
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    path = Path(conf["file"])
    path = path if path.is_absolute() else root / path

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name=name, chips=w["chips"],
                config=json.loads(path.read_text()), config_file=path,
                mix=traffic.load_mix(w["traffic"]),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def load_catalog() -> np.ndarray:
    return np.asarray(json.loads(CATALOG.read_text())["X"], np.float32)


def catalog_targets() -> np.ndarray:
    return np.asarray(json.loads(CATALOG.read_text())["y"], np.float64)


# ------------------------------------------------------------------ server

class Server:
    """The server child and its line protocol (see ``server.py``)."""

    def __init__(self, cmd: list[str], env: dict):
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env)

    def expect(self, word: str) -> dict:
        line = self.proc.stdout.readline().decode()
        if not line.startswith(word):
            self.close()
            raise Refused(f"server: expected {word}, got {line.strip()!r} "
                          f"(exit {self.proc.returncode})")
        rest = line[len(word):].strip()
        return json.loads(rest) if rest else {}

    def send(self, text: str, payload: bytes = b"") -> None:
        self.proc.stdin.write(text.encode() + b"\n" + payload)
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)


def server_env() -> dict:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return env


# ------------------------------------------------------------------- loops

@dataclass
class Sent:
    rows: traffic.Rows
    t0: float            # due (open loop) or sent (closed loop)
    t1: float = float("inf")
    y: np.ndarray | None = None
    error: str | None = None


def closed_loop(clients, mix, drawer, seed, seconds) -> tuple[list, float]:
    out: list[list[Sent]] = [[] for _ in clients]
    start = time.perf_counter()
    end = start + seconds

    def client(c):
        sizes = traffic.closed_sizes(mix, c)
        rng = traffic.rng_for(seed, 1, 3, c)
        k = 0
        while True:
            rows = drawer.rows(int(sizes[k % len(sizes)]), rng)
            t0 = time.perf_counter()
            if t0 >= end:
                return
            s = Sent(rows, t0)
            try:
                s.y = clients[c].predict(rows.X)
            except Exception as exc:     # a failed request counts as failed
                s.error = repr(exc)
            s.t1 = time.perf_counter()
            out[c].append(s)
            k += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [s for per in out for s in per], start


def open_loop(clients, mix, drawer, seed, seconds) -> tuple[list, float, dict]:
    due = traffic.open_schedule(mix, seed, seconds)
    sizes = traffic.closed_sizes(mix, 0)
    sizes = sizes[np.arange(len(due)) % len(sizes)]
    every = drawer.rows(int(sizes.sum()), traffic.rng_for(seed, 1, 3))
    cut = np.r_[0, np.cumsum(sizes)]
    sent = [Sent(every[a:b], 0.0) for a, b in zip(cut[:-1], cut[1:])]
    late = np.zeros(len(due))
    todo: queue.SimpleQueue = queue.SimpleQueue()

    def sender(i):
        client = clients[i % len(clients)]
        while (k := todo.get()) is not None:
            s = sent[k]
            try:
                s.y = client.predict(s.rows.X)
            except Exception as exc:
                s.error = repr(exc)
            s.t1 = time.perf_counter()

    threads = [threading.Thread(target=sender, args=(i,))
               for i in range(mix["senders"])]
    for t in threads:
        t.start()
    start = time.perf_counter()
    for k, d in enumerate(due):
        target = start + d
        # sleep, never spin: a spinning generator takes the senders' GIL
        while (wait := target - time.perf_counter()) > 0:
            time.sleep(wait)
        sent[k].t0 = target
        todo.put(k)
        late[k] = time.perf_counter() - target
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join()
    lateness = {"p50_ms": float(np.percentile(late, 50) * 1e3),
                "p99_ms": float(np.percentile(late, 99) * 1e3),
                "max_ms": float(late.max() * 1e3)}
    return sent, start, lateness


# ------------------------------------------------------------------- check

def check(sent: list[Sent], forest, catalog, seed) -> dict:
    """Compare the answers with ``forest.walk``: every row that repeats a
    catalog row, and ``CHECK_ROWS`` of the fresh ones drawn from the seed
    (all of them where there are fewer)."""
    ok = [s for s in sent if s.error is None and s.y is not None]
    X = np.concatenate([s.rows.X for s in ok]) if ok else np.zeros((0, 12))
    idx = np.concatenate([s.rows.idx for s in ok]) if ok else np.zeros(0, int)
    fresh = (np.concatenate([s.rows.fresh for s in ok]) if ok
             else np.zeros(0, bool))
    got = np.concatenate([s.y for s in ok]) if ok else np.zeros(0)
    seen = ~fresh
    pick = np.flatnonzero(fresh)
    if len(pick) > CHECK_ROWS:
        pick = np.sort(traffic.rng_for(seed, 2).choice(pick, CHECK_ROWS,
                                                       replace=False))
    want = np.r_[pf.walk(forest, catalog)[idx[seen]],
                 pf.walk(forest, X[pick])]
    got = np.r_[got[seen], got[pick]]
    gap = (float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
           if len(got) else float("inf"))
    return {"X": np.r_[X[seen], X[pick]], "got": got, "want": want,
            "rows": len(got),
            "checks": {"max_rel_err": {"value": gap, "limit": MAX_REL_ERR},
                       "unanswered": {"value": len(sent) - len(ok),
                                      "limit": 0}}}


# --------------------------------------------------------------------- run

def per_layer_run(result: dict, forest, catalog, kind) -> dict:
    start, stop = result["marks"]["start"], result["marks"]["stop"]
    delta = {part: {k: v - start[part][k] for k, v in stop[part].items()
                    if isinstance(v, (int, float))}
             for part in ("frontend", "engine", "pool")}
    paths = work.flat_paths(forest, catalog)
    return {"trace": result["trace"], "counters": delta,
            "latency": stop["latency"], "device_kind": kind,
            "work": {"compares_per_row": float(paths.mean()),
                     "nodes": int(len(forest.feature)),
                     "features": forest.n_features}}


@dataclass
class Session:
    """A server warm and serving one seed's forest, and connected clients."""
    server: Server
    forest: pf.Forest
    catalog: np.ndarray
    device: dict
    ready: dict
    clients: list
    setup_s: float


def start(args, cell: Cell, *, require_tpu=True, server_cmd=None) -> Session:
    """Everything before the window: the setup that ``setup_s`` times.
    ``server_cmd`` replaces the server's interpreter and script (tests)."""
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    (CACHE / ".gitignore").write_text("*\n")
    # pool probes send 4 rows, fewer where some are still cached
    warm = sorted({1, 2, 4, *traffic.dispatch_sizes(cell.mix, 64)})
    cmd = (server_cmd or [sys.executable, str(HERE / "server.py")]) + [
        "--config", str(cell.config_file), "--chips", str(cell.chips),
        "--warm", ",".join(map(str, warm)),
        "--trace-dir", str(CACHE / "trace"), "--traced", str(args.trace)]
    server = Server(cmd, server_env())
    try:
        catalog = load_catalog()
        forest = pf.fit(catalog, catalog_targets(),
                        cell.config["n_estimators"], cell.config["max_depth"],
                        traffic.rng_for(cell.config["fit_seed"], 0))
        device = server.expect("DEVICE")
        if require_tpu and (device["platform"] != "tpu"
                            or device["count"] < cell.chips):
            raise Refused(f"no TPU with {cell.chips} chip(s): {device}")
        buf = io.BytesIO()
        forest.save(buf)
        server.send(f"FOREST {len(buf.getvalue())}", buf.getvalue())
        from repro.cluster.remote import RemoteReplica
        ready = server.expect("READY")
        clients = [RemoteReplica(("127.0.0.1", ready["port"]),
                                 timeout_s=LATE_S + args.seconds)
                   for _ in range(cell.mix["clients"])]
        # connect each client with rows the traffic never sends
        hello = traffic.Drawer(dict(cell.mix, perturb=1e-3, fresh=1.0,
                                    draw="uniform"), catalog, args.seed)
        warm_rng = traffic.rng_for(args.seed, 3)
        for c in clients:
            c.predict(hello.rows(cell.mix["rows"]["min"], warm_rng).X)
    except BaseException:
        server.close()
        raise
    return Session(server, forest, catalog, device, ready, clients,
                   time.monotonic() - T0)


def finish(s: Session) -> dict:
    """Close the clients and fetch the server's counters and trace."""
    for c in s.clients:
        c.close()
    s.server.send("END")
    return s.server.expect("RESULT")


def run(args, *, bench=None, require_tpu=True, server_cmd=None) -> dict:
    """One run; returns the result line, details for stderr, the checked
    rows and the forest."""
    bench = bench if bench is not None else json.loads(
        (ROOT / "BENCHMARK.json").read_text())
    cell = load_cell(bench, args.workload)
    s = start(args, cell, require_tpu=require_tpu, server_cmd=server_cmd)
    try:
        drawer = traffic.Drawer(cell.mix, s.catalog, args.seed)
        tracer = None
        if args.trace:
            tracer = threading.Thread(target=trace_window,
                                      args=(s.server, args.seconds))
            tracer.start()
        lateness = None
        if cell.mix["loop"] == "closed":
            sent, t_start = closed_loop(s.clients, cell.mix, drawer,
                                        args.seed, args.seconds)
        else:
            sent, t_start, lateness = open_loop(s.clients, cell.mix, drawer,
                                                args.seed, args.seconds)
        if tracer is not None:
            tracer.join()
        result = finish(s)
        out = summarize(args, cell, s, result, sent, t_start, lateness)
    finally:
        s.server.close()
    return out


def summarize(args, cell: Cell, s: Session, result: dict, sent: list,
              start: float, lateness: dict | None) -> dict:
    done = check(sent, s.forest, s.catalog, args.seed)
    failed = sum(x.error is not None for x in sent)
    e2e = {"latency_s": [x.t1 - x.t0 if x.error is None else float("inf")
                         for x in sent],
           "rows_ok": sum(len(x.rows.X) for x in sent if x.error is None),
           "seconds": max(max((x.t1 for x in sent if x.error is None),
                              default=start) - start, args.seconds),
           "setup_s": s.setup_s}
    if args.trace:
        ctx = per_layer_run(result, s.forest, s.catalog, s.device["kind"])
        wanted = cell.per_layer
    else:
        ctx, wanted = e2e, cell.end_to_end
    metrics = {}
    for m in wanted:
        value = readings.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": s.device["platform"], "kind": s.device["kind"],
           "count": s.device["count"],
           "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": all(c["value"] <= c["limit"]
                           for c in done["checks"].values()),
            "attempted": len(sent), "failed": failed, "metrics": metrics,
            "device": dev}
    if args.trace:
        tr = result["trace"]
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = done["checks"]
    info = {"backend": result["backend"], "warm_s": s.ready["warm_s"],
            "setup_s": s.setup_s,
            "generator_lateness": lateness, "rows_checked": done["rows"],
            "frontend": result["final"]["frontend"],
            "engine": result["final"]["engine"]}
    return {"line": line, "info": info, "check": done, "forest": s.forest}


def trace_window(server: Server, seconds: float) -> None:
    """Trace the window but its first and last ``TRACE_MARGIN_S`` (a quarter
    each in a window shorter than four of them)."""
    margin = min(TRACE_MARGIN_S, seconds / 4)
    span = seconds - 2 * margin
    time.sleep(margin)
    server.send("TRACE_START")
    server.expect("OK")
    time.sleep(span)
    server.send("TRACE_STOP")
    server.expect("OK")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, **kw) -> int:
    args = parse(argv)
    try:
        out = run(args, **kw)
    except Refused as exc:
        print(f"run: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(out["info"]), file=sys.stderr)
    for name, c in out["line"]["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
