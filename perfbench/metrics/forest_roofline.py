"""The forest's share of its roofline, over the engine calls wholly inside
the traced window: the least time of those calls (``work.least_seconds``:
the fitted forest's compares and bytes, whatever the layout) over the
device time of their forest programs."""
from perfbench import work
from perfbench.readings import forest_calls

PROGRAMS = ("_predict_flat_jax", "forest_predict_kernel")


def read(run):
    calls = forest_calls(run, PROGRAMS)
    seconds = sum(c["seconds"] for c in calls)
    if not calls or not seconds:
        return None
    w = run["work"]
    least = sum(work.least_seconds(c["rows"], c["runs"], w["compares_per_row"],
                                   w["nodes"], w["features"],
                                   run["device_kind"]) for c in calls)
    return 100.0 * least / seconds
