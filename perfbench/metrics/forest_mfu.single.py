"""The engine call's share of the chip's roofline in the one-row cell: the
least time (``work.least_seconds``) of the engine calls wholly inside the
traced window that ran anything on the chip, over those calls' host time
(``engine.predict`` from entry to answer: look-up, padding, launch, the
device's work, the wait and the write-back). Taken per call, the arrivals
do not set it. It reads no program's name, so it still bounds
``forest_roofline.single`` when a later program serves the forest by
another kernel."""
from perfbench import work


def read(run):
    tr, w = run["trace"], run["work"]
    calls = [c for c in tr["calls"] if c["whole"]]
    host_s = sum(c["host_s"] for c in calls)
    if not calls or not host_s:
        return None
    least = sum(work.least_seconds(c["rows"], 1, w["compares_per_row"],
                                   w["nodes"], w["features"],
                                   run["device_kind"]) for c in calls)
    return 100.0 * least / host_s
