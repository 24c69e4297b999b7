"""Mean admission-queue wait of the requests the frontend dispatched
(``FrontendStats`` wait_s over waited), between two snapshots taken inside
the traced window."""


def read(run):
    fe = run["counters"]["frontend"]
    if not fe.get("waited"):
        return None
    return fe["wait_s"] / fe["waited"] * 1e3
