"""Rows per batched replica call the frontend made (``FrontendStats``
served over dispatches), between two snapshots taken inside the traced
window."""


def read(run):
    fe = run["counters"]["frontend"]
    if not fe["dispatches"]:
        return None
    return fe["served"] / fe["dispatches"]
