"""The readers of the frontend's wait and merging, the backend's padding
and the engine cache's hits: counter deltas of the traced window, and
nothing where the program has no such counter or counted nothing. The
server's engine counters, summed over its engines. And the median latency,
by nearest rank."""
import dataclasses

import pytest

from perfbench import readings, server

WAIT, PADDED = "frontend_wait_ms.batch", "padded_row_share.batch"
ONE_ROW = ("rows_per_dispatch.single", "frontend_wait_ms.single",
           "engine_hit_share.repeat")


def run(frontend, engine):
    return {"counters": {"frontend": frontend, "engine": engine,
                         "pool": {}}}


def test_counter_readers_divide_their_deltas():
    r = run({"dispatches": 2, "served": 20, "wait_s": 0.5, "waited": 4},
            {"backend_rows": 5, "padded_rows": 3})
    assert readings.reader(WAIT)(r) == pytest.approx(125.0)
    assert readings.reader(PADDED)(r) == pytest.approx(37.5)


@pytest.mark.parametrize("frontend,engine", [
    # a program without the counters
    ({"dispatches": 2, "served": 20}, {"backend_rows": 5}),
    # counters that counted nothing in the window
    ({"dispatches": 0, "served": 0, "wait_s": 0.0, "waited": 0},
     {"backend_rows": 0, "padded_rows": 0}),
], ids=["absent", "zero"])
def test_counter_readers_read_nothing_without_counts(frontend, engine):
    r = run(frontend, engine)
    assert readings.reader(WAIT)(r) is None
    assert readings.reader(PADDED)(r) is None


@pytest.mark.parametrize("name,want", zip(ONE_ROW, (10.0, 125.0, 97.5)))
def test_one_row_cell_readers_divide_their_deltas(name, want):
    r = run({"dispatches": 2, "served": 20, "wait_s": 0.5, "waited": 4},
            {"cache_hits": 39, "cache_misses": 1})
    assert readings.reader(name)(r) == pytest.approx(want)


@pytest.mark.parametrize("name", ONE_ROW)
def test_one_row_cell_readers_read_nothing_without_counts(name):
    r = run({"dispatches": 0, "served": 0, "wait_s": 0.0, "waited": 0},
            {"cache_hits": 0, "cache_misses": 0})
    assert readings.reader(name)(r) is None


@pytest.mark.parametrize("latency_s,want_ms", [
    ([0.004, 0.001, float("inf"), 0.003, 0.002], 3.0),   # rank ceil(2.5)
    ([0.004, 0.001, float("inf"), 0.002], 2.0),          # rank 2 of 4
    ([float("inf"), 0.001, float("inf")], float("inf")),  # most unanswered
], ids=["odd", "even", "unanswered"])
def test_p50_is_the_nearest_rank_median(latency_s, want_ms):
    assert readings.reader("p50_ms")({"latency_s": latency_s}) == want_ms


class Stub:
    """Something with a counters snapshot, as engine, frontend and pool."""

    def __init__(self, stats):
        self.stats = stats

    def stats_snapshot(self):
        return self.stats

    def latency_summary(self):
        return {}


def test_engine_counters_are_summed_over_engines():
    from repro.cluster.frontend import FrontendStats
    from repro.cluster.replicas import PoolStats
    from repro.serve.engine import EngineStats
    front, pool = Stub(FrontendStats()), Stub(PoolStats())
    a = EngineStats(batches=2, backend_rows=10, padded_rows=6)
    b = EngineStats(batches=3, backend_rows=7, cache_hits=4)
    one = server.counters(front, {"y": Stub(a)}, pool)
    assert one["engine"] == dataclasses.asdict(a)
    assert one["engines"] == {"y": dataclasses.asdict(a)}
    two = server.counters(front, {"t": Stub(a), "p": Stub(b)}, pool)
    assert two["engine"] == dataclasses.asdict(EngineStats(
        batches=5, backend_rows=17, padded_rows=6, cache_hits=4))
    assert list(two["engines"]) == ["t", "p"]
