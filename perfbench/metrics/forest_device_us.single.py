"""Device time of the forest's programs per engine call, over the engine
calls wholly inside the traced window. Per call, not per row: a call of
one row pays for the whole level walk, whatever its rows."""
from perfbench.readings import forest_calls

PROGRAMS = ("_predict_flat_jax", "forest_predict_kernel")


def read(run):
    calls = forest_calls(run, PROGRAMS)
    if not calls:
        return None
    return sum(c["seconds"] for c in calls) / len(calls) * 1e6
