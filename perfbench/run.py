"""One run of one benchmark cell: the load process.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``. This process fits the configuration's
forests (``forest.fit``) on the rows of its ``catalog``: where it names no
``forests``, one forest on the catalog's column ``y`` from stream 0 of its
``fit_seed``; else one forest per entry (``device``, ``target``:
``time_us`` or ``power_w``, ``column`` of its ``targets`` file), each from
the stream of ``fit_seed`` that its column names. It starts ``server.py``
(the one process that touches JAX's backend, and so the chip), hands it
the forests, and once the server is warm drives the mix through
``RemoteReplica`` clients over loopback for ``--seconds``, timing every
request on the client side. A request is the mix's ``op``: ``predict``
(the default) or ``schedule`` (its rows a queue of kernels, with the
mix's ``objective`` and ``deadline_ms``). The seed draws the rows, their
noise and their order.

With ``--trace 1`` the server also records the profiler's trace over the
window less ``TRACE_MARGIN_S`` at each end, and the line carries the
per-layer metrics; otherwise the end-to-end ones. After the window every
answer due is awaited (up to ``LATE_S``), the server is shut down, and the
answers are checked against plain references that import nothing of the
program. ``predict`` answers against the walk of the same forest
(``forest.walk``): every row that repeats a catalog row, and
``CHECK_ROWS`` fresh rows drawn from the seed. ``schedule`` answers, whole
requests drawn from the seed up to ``CHECK_KERNELS`` kernels, the longest
among them, against the walks of every forest and the schedule that
``schedule.py`` makes from them (``check_schedule``). The last line of
stdout is the result, as JSON; the last lines of stderr are the numbers
compared, each with its limit.

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
import zlib  # noqa: E402
from collections import Counter  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import forest as pf  # noqa: E402
from perfbench import readings, traffic, work  # noqa: E402
from perfbench import schedule as ref_schedule  # noqa: E402

CACHE = HERE / ".cache"
CATALOG = HERE / "data" / "suite_catalog.json"
CHECK_ROWS = 8192        # rows checked when the mix's rows never repeat
CHECK_KERNELS = 4096     # kernels of the schedule requests checked
LATE_S = 60.0            # how long answers due in the window are awaited
TRACE_MARGIN_S = 1.0     # untraced start and end of a --trace 1 window
#: Largest relative gap |served - walk| / max(|walk|, 1) that passes.
MAX_REL_ERR = 1e-5
#: Largest relative gap between a schedule's reported makespan, energy and
#: queue starts and those its own assignments give (float64 rounding).
MAX_TOTALS_ERR = 1e-9
#: Largest relative gap between a schedule's objective and the reference
#: schedule's on the walked forests (PERF.md §2 gives its readings).
MAX_OBJECTIVE_GAP = 1e-4


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


def load_catalog(path: Path = CATALOG) -> np.ndarray:
    return np.asarray(json.loads(path.read_text())["X"], np.float32)


def catalog_targets(path: Path = CATALOG) -> np.ndarray:
    return np.asarray(json.loads(path.read_text())["y"], np.float64)


def load_targets(config: dict) -> dict:
    """The configuration's ``targets`` file: ``columns`` by name, and each
    device's ``freq_grid`` under ``devices``."""
    return json.loads((ROOT / config["targets"]).read_text())


def column_stream(column: str) -> int:
    """The stream of ``fit_seed`` that the forest of ``column`` is fitted
    from: the same for a column whatever else the configuration holds."""
    return zlib.crc32(column.encode())


def fit_forests(config: dict, catalog: np.ndarray) -> dict:
    """The configuration's forests by the column each is fitted on, in the
    order of its ``forests`` (see the module's docstring)."""
    fit = (config["n_estimators"], config["max_depth"])
    seed = config["fit_seed"]
    if "forests" not in config:
        y = catalog_targets(ROOT / config["catalog"])
        return {"y": pf.fit(catalog, y, *fit, traffic.rng_for(seed, 0))}
    columns = load_targets(config)["columns"]
    named = [e["column"] for e in config["forests"]]
    return {c: pf.fit(catalog, np.asarray(columns[c]), *fit,
                      traffic.rng_for(seed, 0, column_stream(c)))
            for c in named}


@dataclass
class Devices:
    """A ``forests`` configuration's devices, in the order they first
    appear: each one's time and power columns and its DVFS grid."""
    names: list
    time: list
    power: list
    grids: list

    @classmethod
    def of(cls, config: dict) -> "Devices":
        by = {}
        for e in config["forests"]:
            by.setdefault(e["device"], {})[e["target"]] = e["column"]
        grids = load_targets(config)["devices"]
        return cls(list(by), [c["time_us"] for c in by.values()],
                   [c["power_w"] for c in by.values()],
                   [tuple(grids[d]["freq_grid"]) for d in by])


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


def requester(mix: dict):
    """``(client, X) -> answer``: the mix's ``op``, ``predict`` (the
    default) or ``schedule`` with its ``objective`` and ``deadline_ms``."""
    op = mix.get("op", "predict")
    if op == "predict":
        return lambda client, X: client.predict(X)
    if op != "schedule":
        raise ValueError(f"unknown op {op!r}")
    deadline_ms = mix.get("deadline_ms")
    kw = {"objective": mix["objective"],
          "deadline_s": None if deadline_ms is None else deadline_ms / 1e3}
    return lambda client, X: client.schedule(X, **kw)


def closed_loop(clients, mix, drawer, seed, seconds) -> tuple[list, float]:
    send = requester(mix)
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
                s.y = send(clients[c], rows.X)
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
    send = requester(mix)
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
                s.y = send(client, s.rows.X)
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


def check_schedule(sent: list[Sent], forests: dict, devices: Devices,
                   mix: dict, seed) -> dict:
    """Compare ``schedule`` answers with plain references: whole requests
    drawn from the seed, up to ``CHECK_KERNELS`` kernels, the longest
    always among them. Every forest is walked over their rows
    (``forest.walk``), which gives each kernel's nominal time (``exp`` of
    the walked log time) and power on each device; ``compare_schedule``
    reads each answer against them."""
    ok = [s for s in sent if s.error is None and s.y is not None]
    order = list(traffic.rng_for(seed, 2).permutation(len(ok)))
    if ok:
        longest = max(range(len(ok)), key=lambda i: len(ok[i].rows.X))
        order = [longest, *(i for i in order if i != longest)]
    picked, kernels = [], 0
    for i in order:
        if picked and kernels + len(ok[i].rows.X) > CHECK_KERNELS:
            continue
        picked.append(ok[i])
        kernels += len(ok[i].rows.X)
    X = (np.concatenate([s.rows.X for s in picked]) if picked
         else np.zeros((0, 12), np.float32))
    t_nom = np.exp(np.stack([pf.walk(forests[c], X) for c in devices.time],
                            axis=1))
    p_nom = np.stack([pf.walk(forests[c], X) for c in devices.power], axis=1)
    cut = np.cumsum([0, *(len(s.rows.X) for s in picked)])
    read = []
    for s, lo, hi in zip(picked, cut[:-1], cut[1:]):
        try:
            read.append(compare_schedule(s.y, t_nom[lo:hi], p_nom[lo:hi],
                                         devices, mix))
        except (KeyError, TypeError, ZeroDivisionError):  # malformed
            read.append({"bad_assignments": hi - lo, "deadline_mismatch": 1,
                         "max_rel_err": float("inf"),
                         "totals_rel_err": float("inf"),
                         "objective_gap": float("inf")})
    worst = {k: max((r[k] for r in read), default=float("inf"))
             for k in ("max_rel_err", "totals_rel_err", "objective_gap")}
    return {"X": X, "t_nom": t_nom, "p_nom": p_nom, "cut": cut,
            "rows": kernels,
            "checks": {
                "bad_assignments": {"value": sum(r["bad_assignments"]
                                                 for r in read),
                                    "limit": 0},
                "max_rel_err": {"value": worst["max_rel_err"],
                                "limit": MAX_REL_ERR},
                "totals_rel_err": {"value": worst["totals_rel_err"],
                                   "limit": MAX_TOTALS_ERR},
                "objective_gap": {"value": worst["objective_gap"],
                                  "limit": MAX_OBJECTIVE_GAP},
                "deadline_mismatch": {"value": sum(r["deadline_mismatch"]
                                                   for r in read),
                                      "limit": 0},
                "unanswered": {"value": len(sent) - len(ok), "limit": 0}}}


def compare_schedule(answer: dict, t_nom, p_nom, devices: Devices,
                     mix: dict) -> dict:
    """One schedule answer read against the nominal times and powers of
    its kernels on each device (kernels, devices):

    bad_assignments   kernels not assigned exactly once, plus assignments
                      not to a configured device's one queue at a clock
                      of its grid
    max_rel_err       each assignment's ``t_us`` and ``power_w`` against
                      the nominal time over its clock and the nominal
                      power times the clock cubed, as ``check`` measures
    totals_rel_err    queue starts that overlap, and the reported
                      ``makespan_us`` and ``energy_j`` against those the
                      assignments give, over that makespan and energy
    objective_gap     the objective against that of ``schedule.py``'s
                      schedule on the same times and powers
    deadline_mismatch 1 where ``meets_deadline`` is not the reference
                      schedule's
    """
    n = len(t_nom)
    assigned = answer.get("assignments", [])
    count = Counter(a.get("kernel") for a in assigned)
    bad = (sum(count[k] != 1 for k in range(n))
           + sum(c for k, c in count.items() if k not in range(n)))
    index = {name: j for j, name in enumerate(devices.names)}
    rel, queues = 0.0, {}
    for a in assigned:
        j, f, k = index.get(a.get("device")), a.get("freq"), a.get("kernel")
        if j is None or a.get("queue_slot") != 0 or f not in devices.grids[j]:
            bad += 1
        elif k in range(n):
            want_t, want_p = t_nom[k, j] / f, p_nom[k, j] * f ** 3
            rel = max(rel, abs(a["t_us"] - want_t) / max(abs(want_t), 1.0),
                      abs(a["power_w"] - want_p) / max(abs(want_p), 1.0))
            queues.setdefault(j, []).append((a["start_us"], a["t_us"]))
    spans = [sorted(q) for q in queues.values()]
    makespan = max((st + t for q in spans for st, t in q), default=0.0)
    energy = sum(a["power_w"] * a["t_us"] for a in assigned) * 1e-6
    overlap = max((st0 + t0 - st1 for q in spans
                   for (st0, t0), (st1, _) in zip(q, q[1:])), default=0.0)
    totals = max(overlap / makespan,
                 abs(answer["makespan_us"] - makespan) / makespan,
                 abs(answer["energy_j"] - energy) / energy)
    want = ref_schedule.schedule(t_nom, p_nom, devices.grids,
                                 mix["objective"], deadline_us(mix))
    goal = objective(want, mix["objective"])
    return {"bad_assignments": bad, "max_rel_err": float(rel),
            "totals_rel_err": float(totals),
            "objective_gap": float(abs(objective(answer, mix["objective"])
                                       - goal) / goal),
            "deadline_mismatch": int(answer["meets_deadline"]
                                     != want["meets_deadline"])}


def deadline_us(mix: dict) -> float | None:
    deadline_ms = mix.get("deadline_ms")
    return None if deadline_ms is None else deadline_ms * 1e3


def objective(answer: dict, name: str) -> float:
    """A schedule's value under its objective: makespan (µs), energy (J),
    or their product for ``edp``."""
    return {"makespan": answer["makespan_us"], "energy": answer["energy_j"],
            "edp": answer["makespan_us"] * answer["energy_j"]}[name]


# --------------------------------------------------------------------- run

def per_layer_run(result: dict, forests: dict, catalog, kind) -> dict:
    """The per-layer metrics' ``run``. Its ``work`` is a forest call's:
    the one forest's, or the mean over the forests, each of which a
    schedule request calls once with the same rows."""
    start, stop = result["marks"]["start"], result["marks"]["stop"]

    def delta(a: dict, b: dict) -> dict:
        return {k: v - a[k] for k, v in b.items()
                if isinstance(v, (int, float))}

    counts = {part: delta(start[part], stop[part])
              for part in ("frontend", "engine", "pool")}
    counts["engines"] = {name: delta(start["engines"][name], snap)
                         for name, snap in stop["engines"].items()}
    fs = list(forests.values())
    return {"trace": result["trace"], "counters": counts,
            "latency": stop["latency"], "device_kind": kind,
            "work": {"compares_per_row": float(np.mean(
                         [work.flat_paths(f, catalog).mean() for f in fs])),
                     "nodes": int(round(np.mean([len(f.feature)
                                                 for f in fs]))),
                     "features": fs[0].n_features}}


@dataclass
class Session:
    """A server warm and serving the configuration's forests (by column,
    see ``fit_forests``), and connected clients."""
    server: Server
    forests: dict
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
    # the frontend merges up to 64 queued predict requests into one call;
    # a schedule request calls each forest once with its own rows
    merged = 64 if cell.mix.get("op", "predict") == "predict" else 1
    cmd = (server_cmd or [sys.executable, str(HERE / "server.py")]) + [
        "--config", str(cell.config_file), "--chips", str(cell.chips),
        "--warm", ",".join(map(str, traffic.dispatch_sizes(cell.mix,
                                                           merged))),
        # one directory per load process: runs side by side (tests) would
        # otherwise clear each other's trace
        "--trace-dir", str(CACHE / f"trace-{os.getpid()}"),
        "--traced", str(args.trace)]
    server = Server(cmd, server_env())
    try:
        catalog = load_catalog(ROOT / cell.config["catalog"])
        forests = fit_forests(cell.config, catalog)
        device = server.expect("DEVICE")
        if require_tpu and (device["platform"] != "tpu"
                            or device["count"] < cell.chips):
            raise Refused(f"no TPU with {cell.chips} chip(s): {device}")
        for forest in forests.values():
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
        send = requester(cell.mix)
        for c in clients:
            send(c, hello.rows(cell.mix["rows"]["min"], warm_rng).X)
    except BaseException:
        server.close()
        raise
    return Session(server, forests, catalog, device, ready, clients,
                   time.monotonic() - T0)


def finish(s: Session) -> dict:
    """Close the clients and fetch the server's counters and trace."""
    for c in s.clients:
        c.close()
    s.server.send("END")
    return s.server.expect("RESULT")


def run(args, *, bench=None, require_tpu=True, server_cmd=None) -> dict:
    """One run; returns the result line, details for stderr, the check
    (what was compared) and the forests by column."""
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
    if cell.mix.get("op", "predict") == "schedule":
        done = check_schedule(sent, s.forests, Devices.of(cell.config),
                              cell.mix, args.seed)
    else:
        done = check(sent, *s.forests.values(), s.catalog, args.seed)
    failed = sum(x.error is not None for x in sent)
    e2e = {"latency_s": [x.t1 - x.t0 if x.error is None else float("inf")
                         for x in sent],
           "rows_ok": sum(len(x.rows.X) for x in sent if x.error is None),
           "seconds": max(max((x.t1 for x in sent if x.error is None),
                              default=start) - start, args.seconds),
           "setup_s": s.setup_s}
    if args.trace:
        ctx = per_layer_run(result, s.forests, s.catalog, s.device["kind"])
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
    return {"line": line, "info": info, "check": done, "forests": s.forests}


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
