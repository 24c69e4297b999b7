"""Device time of the forest's programs per row, over the engine calls
wholly inside the traced window: rows and time are read from the same
calls. The rows are those the engine was given; in the batch mix every
row is fresh, so the engine sends each of them to the backend."""
from perfbench.readings import forest_calls

PROGRAMS = ("_predict_flat_jax", "forest_predict_kernel")


def read(run):
    calls = forest_calls(run, PROGRAMS)
    rows = sum(c["rows"] for c in calls)
    if not rows:
        return None
    return sum(c["seconds"] for c in calls) / rows * 1e6
