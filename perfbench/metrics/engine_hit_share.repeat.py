"""Share of the rows the engine was given that its cache answered
(``EngineStats`` cache_hits over cache_hits plus cache_misses), between
two snapshots taken inside the traced window."""


def read(run):
    eng = run["counters"]["engine"]
    looked_up = eng.get("cache_hits", 0) + eng.get("cache_misses", 0)
    if not looked_up:
        return None
    return 100.0 * eng["cache_hits"] / looked_up
