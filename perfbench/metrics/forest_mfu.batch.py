"""The whole served window's share of the chip's roofline: the least time
(``work.least_seconds``) of the engine calls that ran anything on the chip,
each weighted by the share of it inside the traced window, over the
window's wall time. It reads no program's name, so it still bounds a claim
when a later program serves the forest by another kernel."""
from perfbench import work


def read(run):
    tr, w = run["trace"], run["work"]
    if not tr["devices"] or not tr["window_s"] or not tr["calls"]:
        return None
    least = sum(c["inside"] * work.least_seconds(
        c["rows"], 1, w["compares_per_row"], w["nodes"], w["features"],
        run["device_kind"]) for c in tr["calls"])
    return 100.0 * least / tr["window_s"]
