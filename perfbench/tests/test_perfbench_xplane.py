"""The trace reduction: busy and idle time, the engine's calls with their
rows and programs, idle gaps by what the host was doing; and the readers
of the forest's metrics over those calls."""
from types import SimpleNamespace as NS

import pytest

from perfbench import xplane


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats.items()))


def plane(name, lines, stats=()):
    return NS(name=name, stats=list(stats),
              lines=[NS(name=k, events=v) for k, v in lines.items()])


PROG = "jit__predict_flat_jax(1)"


def fake_trace():
    """A 1000 ns window inside a longer session: the chip runs programs in
    100-300 and 500-600 (ops overlap inside the first), 950-1050 across
    the window's end and 1200-1300 after it. The engine calls them with 8,
    64, 32 and 16 rows; a probe of 4 rows, which the cache answers, runs
    inside the first call. The host dispatches in 0-100 and waits in
    300-500."""
    chip = plane("/device:TPU:0", {
        "XLA Modules": [ev(PROG, 100, 200), ev(PROG, 500, 100),
                        ev(PROG, 950, 100), ev(PROG, 1200, 100)],
        "XLA Ops": [ev("%gather = s32[8] gather(...)", 100, 150),
                    ev("%select = s32[8] select(...)", 200, 100),
                    ev("%gather = s32[64] gather(...)", 500, 100),
                    ev("%late = s32[64] copy(...)", 1200, 100)]})
    host = plane("/host:CPU", {"python3": [
        ev(xplane.WINDOW, 0, 1000),
        ev("PjitFunction(_predict_flat_jax)", 0, 100),
        ev(xplane.CALL, 0, 350, rows=8),
        ev(xplane.CALL, 120, 10, rows=4),
        ev("wait", 300, 200),
        ev(xplane.CALL, 480, 140, rows=64),
        ev(xplane.CALL, 940, 120, rows=32),
        ev(xplane.CALL, 1150, 250, rows=16)]})
    env = plane("Task Environment", {}, [("profile_start_time", 10_000),
                                         ("profile_stop_time", 12_000)])
    return NS(planes=[host, chip, env])


def test_busy_programs_and_ops():
    out = xplane.reduce(fake_trace())
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(300e-9)      # 100-300 and 500-600
    assert out["device_ops"][0] == ["%gather", pytest.approx(250e-9)]
    calls = out["calls"]            # the probe ran nothing; 16 rows came late
    assert [(c["rows"], c["whole"]) for c in calls] == [
        (8, True), (64, True), (32, False)]
    assert [c["inside"] for c in calls] == pytest.approx([1.0, 1.0, 0.5])
    assert [c["host_s"] for c in calls] == pytest.approx(
        [350e-9, 140e-9, 120e-9])
    assert [c["programs"][PROG]["seconds"] for c in calls] == pytest.approx(
        [200e-9, 100e-9, 100e-9])
    assert all(c["programs"][PROG]["runs"] == 1 for c in calls)


def test_forest_readers_count_rows_and_time_over_the_same_calls():
    from perfbench import readings, work
    run = {"trace": xplane.reduce(fake_trace()), "device_kind": "TPU v5 lite",
           "work": {"compares_per_row": 10.0, "nodes": 1000, "features": 12}}
    # the two calls wholly inside: 300 ns of device time for 72 rows
    per_row = readings.reader("forest_device_us_per_row.batch")(run)
    assert per_row == pytest.approx(300e-9 / 72 * 1e6)

    def least(rows):
        return work.least_seconds(rows, 1, 10.0, 1000, 12, "TPU v5 lite")

    roof = readings.reader("forest_roofline")(run)
    assert roof == pytest.approx(100 * (least(8) + least(64)) / 300e-9)
    # the whole window: half of the call across its end counts
    mfu = readings.reader("forest_mfu.batch")(run)
    assert mfu == pytest.approx(
        100 * (least(8) + least(64) + 0.5 * least(32)) / 1000e-9)


def test_one_row_cell_readers_over_the_same_calls():
    from perfbench import readings, work
    run = {"trace": xplane.reduce(fake_trace()), "device_kind": "TPU v5 lite",
           "work": {"compares_per_row": 10.0, "nodes": 1000, "features": 12}}
    # the two calls wholly inside: 300 ns of device time, per call
    per_call = readings.reader("forest_device_us.single")(run)
    assert per_call == pytest.approx(300e-9 / 2 * 1e6)
    for name, same in (("forest_roofline.single", "forest_roofline"),
                       ("device_idle_share.single",
                        "device_idle_share.batch")):
        assert readings.reader(name)(run) == readings.reader(same)(run)
    assert readings.reader("device_idle_share.single")(run) == (
        pytest.approx(70.0))
    # the same two calls' least time over their 350 + 140 ns of host time,
    # which bounds the roofline over their 300 ns of device time
    least = sum(work.least_seconds(rows, 1, 10.0, 1000, 12, "TPU v5 lite")
                for rows in (8, 64))
    mfu = readings.reader("forest_mfu.single")(run)
    assert mfu == pytest.approx(100 * least / 490e-9)
    assert mfu < readings.reader("forest_roofline.single")(run)


def test_idle_gaps_are_named_by_the_innermost_host_event():
    gaps = dict(xplane.reduce(fake_trace())["idle_gaps"])
    assert gaps["PjitFunction(_predict_flat_jax)"] == pytest.approx(100e-9)
    assert gaps["wait"] == pytest.approx(200e-9)
    assert gaps["no host event"] == pytest.approx(400e-9)  # 600-1000
    assert sum(gaps.values()) == pytest.approx(700e-9)


def test_a_trace_without_a_chip_reads_no_device():
    out = xplane.reduce(NS(planes=[plane("/host:CPU", {"t": []})]))
    assert out["devices"] == 0 and out["busy_s"] == 0.0


def test_recorded_chip_trace():
    """Three calls each of 64 and 1024 rows through the compiled Pallas
    kernel, traced on one TPU v5e with the profiler alone; it has no
    window event, so the session is the window."""
    from pathlib import Path
    out = xplane.reduce_file(Path(__file__).parent / "data"
                             / "forest_predict.xplane.pb")
    assert out["devices"] == 1
    assert out["calls"] == []       # no engine call was annotated
    assert out["device_ops"][0][0] == "%forest_predict.1"
    assert 0 < out["busy_s"] < out["window_s"] < 1.0
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
