"""The readers of the frontend's wait and the backend's padding: counter
deltas of the traced window, and nothing where the program has no such
counter or counted nothing."""
import pytest

from perfbench import readings

WAIT, PADDED = "frontend_wait_ms.batch", "padded_row_share.batch"


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
