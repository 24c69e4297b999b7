"""Finding and reading the metrics, and the statistics they share.

Each metric of ``BENCHMARK.json`` is a file ``metrics/<name>.py`` with one
function ``read(run) -> float | None``. ``run`` is a dict:

  end-to-end metrics (``--trace 0``):
    latency_s   per request, seconds: answered minus due (open loop) or
                minus sent (closed loop); ``inf`` for a request not answered
    rows_ok     rows answered, of every request sent in the window
    seconds     window's start to the last of those answers (at least the
                window's length)
    setup_s     process start to the window's start

  per-layer metrics (``--trace 1``), over the traced part of the window:
    trace       ``xplane.reduce_dir``'s summary of the profiler's trace
    counters    frontend, engine and pool counters: the snapshot taken
                just before the window's close minus the one just after
                its open
    latency     the frontend's ``latency_summary()`` at the stop
    work        compares per row, real nodes, features of the forest
    device_kind the chip's ``device_kind``

A reader that finds nothing to read returns ``None`` and the metric is left
out of the line.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

METRICS = Path(__file__).resolve().parent / "metrics"


def reader(name: str):
    path = METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def nearest_rank(values, pct: float) -> float:
    """The ``pct``-th percentile by nearest rank: an observed value."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    return xs[max(math.ceil(pct / 100.0 * len(xs)) - 1, 0)]


def forest_calls(run, names) -> list[dict]:
    """The traced calls wholly inside the window that ran a program whose
    name contains one of ``names``: {rows, seconds, runs} of those
    programs."""
    out = []
    for c in run["trace"]["calls"]:
        hits = [v for k, v in c["programs"].items()
                if any(n in k for n in names)]
        if c["whole"] and hits:
            out.append({"rows": c["rows"],
                        "seconds": sum(h["seconds"] for h in hits),
                        "runs": sum(h["runs"] for h in hits)})
    return out

