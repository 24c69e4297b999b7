"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData``; nothing but JAX is needed. The
window is the host event ``WINDOW`` that the server process holds open
from just after the profiler starts to just before it stops; the
profiler's own session (``Task Environment``) also covers its start and
stop, about a quarter of a second on a TPU v5e. Event times
are relative to the session's start. On each TPU device plane:

  devices     how many TPU device planes the trace has
  busy_s      the union of the intervals of the line ``XLA Ops`` (an
              operation running on the chip), clipped to the window and
              averaged over the chips that ran anything
  calls       the server's engine calls (host events ``CALL``, each with
              the stat ``rows``) that overlap the window and ran a program
              (line ``XLA Modules``) on the chip: rows, whether the call
              lies wholly inside the window, its host seconds and the
              share of them inside, and per program its device seconds
              and runs. A
              program run belongs to the call whose host event overlaps
              it most, so rows and device time are counted over the same
              calls
  device_ops  the 10 operations with most device time, [name, seconds],
              named by their HLO instruction (all shapes of it together)
  idle_gaps   the chip's idle time, attributed to what the host was doing
              at the middle of each gap (the innermost host event there,
              ``no host event`` where the trace has none: the host ran
              untraced Python or waited): the 10 largest totals,
              [name, seconds]
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

WINDOW = "perfbench.window"
CALL = "engine.predict"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def reduce_dir(path) -> dict:
    found = sorted(Path(path).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return reduce_file(found[-1])


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) intervals into disjoint sorted ones."""
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > ends[:-1]]
    starts = iv[new, 0]
    last = np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def _window(pd) -> tuple[float, float] | None:
    """(start, end) ns of the ``WINDOW`` event, else of the session."""
    session = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        return e.start_ns, e.start_ns + e.duration_ns
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                session = (0.0, float(st["profile_stop_time"])
                           - float(st["profile_start_time"]))
    return session


def reduce(pd) -> dict:
    win = _window(pd)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    modules = []
    ops: dict[str, float] = {}
    chip_busy = []
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        modules += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in lines.get(MODULES_LINE, [])]
        evs = lines.get(OPS_LINE, [])
        for e in evs:      # "%fusion.3 = f32[...] fusion(...)": the name
            name = e.name.split(" = ")[0]
            ops[name] = ops.get(name, 0.0) + e.duration_ns * 1e-9
        if evs:
            chip_busy.append(_union(np.array(
                [[e.start_ns, e.start_ns + e.duration_ns] for e in evs],
                np.float64)))
    if win is None:        # no session stats: the span the chip was busy
        win = ((min(iv[0, 0] for iv in chip_busy),
                max(iv[-1, 1] for iv in chip_busy)) if chip_busy
               else (0.0, 0.0))
    lo, hi = win
    chip_busy = [np.clip(iv, lo, hi) for iv in chip_busy]
    busy = [float((iv[:, 1] - iv[:, 0]).sum()) for iv in chip_busy]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": float(np.mean(busy)) * 1e-9 if busy else 0.0,
            "devices": len(devices), "calls": _calls(pd, modules, lo, hi),
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": (_idle_gaps(pd, chip_busy[0], lo, hi)
                          if chip_busy else [])}


def _calls(pd, modules: list, lo: float, hi: float) -> list:
    hosts = [(e.start_ns, e.start_ns + e.duration_ns,
              int(dict(e.stats).get("rows", 0)))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events if e.name == CALL]
    modules = sorted(modules)
    starts = np.array([m[0] for m in modules], np.float64)
    best = np.zeros(len(modules))
    owner = np.full(len(modules), -1)
    for i, (a, b, _) in enumerate(hosts):
        for m in range(np.searchsorted(starts, a),
                       np.searchsorted(starts, b, side="right")):
            s, e, _ = modules[m]
            overlap = min(b, e) - max(a, s)
            if overlap > best[m]:
                best[m], owner[m] = overlap, i
    calls: dict[int, dict] = {}
    for (s, e, name), i in zip(modules, owner):
        if i < 0:
            continue
        a, b, rows = hosts[i]
        if b <= lo or a >= hi:
            continue
        c = calls.setdefault(i, {
            "rows": rows, "whole": bool(a >= lo and b <= hi),
            "host_s": (b - a) * 1e-9,
            "inside": (min(b, hi) - max(a, lo)) / max(b - a, 1.0),
            "programs": {}})
        p = c["programs"].setdefault(name, {"seconds": 0.0, "runs": 0})
        p["seconds"] += (e - s) * 1e-9
        p["runs"] += 1
    return [calls[i] for i in sorted(calls)]


def _idle_gaps(pd, busy: np.ndarray, lo: float, hi: float) -> list:
    """Idle time of the first chip, summed by what the host was doing."""
    edges = np.r_[lo, busy.ravel(), hi]
    gaps = edges.reshape(-1, 2)
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    if not len(gaps):
        return []
    mids = gaps.mean(axis=1)
    order = np.argsort(mids)
    mids_sorted = mids[order]
    best = np.full(len(gaps), np.inf)
    label = np.array(["no host event"] * len(gaps), dtype=object)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns <= 0 or e.name == WINDOW:
                    continue
                a = np.searchsorted(mids_sorted, e.start_ns)
                b = np.searchsorted(mids_sorted, e.start_ns + e.duration_ns,
                                    side="right")
                if a == b:
                    continue
                hit = order[a:b]
                inner = e.duration_ns < best[hit]
                best[hit[inner]] = e.duration_ns
                label[hit[inner]] = e.name
    totals: dict[str, float] = {}
    for name, (a, b) in zip(label, gaps):
        totals[name] = totals.get(name, 0.0) + (b - a) * 1e-9
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[k, v] for k, v in top]
