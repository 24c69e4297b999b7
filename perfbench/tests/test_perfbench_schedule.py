"""Cells of ``schedule`` requests on the CPU, with a tiny configuration:
two simulated devices with a time and a power forest each (16 trees of
depth at most 8) behind ``MultiDeviceEngine``, and mixes of queues of
16-64 kernels under the objectives ``energy`` and ``makespan``. A sound
run is correct, and a run whose timed path is broken underneath is not.
The server child here skips the look for a chip; everything else is the
run as the benchmark makes it, over loopback.

And the plain reference schedule (``perfbench/schedule.py``) makes the
program's schedule, assignment for assignment, on the same times and
powers."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import run, traffic
from perfbench import schedule as ref_schedule

ROOT = Path(run.__file__).resolve().parents[1]
CONFIG = ROOT / "perfbench/tests/data/pricing-2dev.json"
MIX = {"loop": "open", "rate_per_s": 16, "clients": 2, "senders": 4,
       "rows": {"min": 16, "max": 64}, "draw": "uniform", "perturb": 0.001,
       "op": "schedule", "objective": "energy"}
OBJECTIVES = ("energy", "makespan")

CHILD = """
import sys
sys.path[:0] = {paths!r}
import numpy as np
import jax.numpy as jnp
import repro.core.forest_jax as fj
from repro.cluster import ClusterFrontend
from repro.serve import MultiDeviceEngine
import perfbench.server as server

fault = {fault!r}
made = []
init, call, schedule = (fj.FlatForestJax.__init__, fj.FlatForestJax.__call__,
                        ClusterFrontend.schedule)
engines_init = MultiDeviceEngine.__init__


def init_(self, *a, **kw):              # forests are built in entry order
    init(self, *a, **kw)
    made.append(self)


def call_(self, x):
    x = jnp.asarray(x, jnp.float32)
    if fault == "bf16_control":         # every forest walked in bfloat16
        f, thr, left, right, val, roots = self.arrays
        bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return fj._predict_flat_jax(f, bf(thr), left, right, bf(val), roots,
                                    bf(x), max_depth=self.max_depth)
    y = np.array(call(self, x))
    if fault == "forest_offset" and self is made[0]:
        y += 1e-3                       # the first forest's answers
    return y


def engines_init_(self, engines, **kw):  # the last device left out
    engines_init(self, dict(list(engines.items())[:-1]), **kw)


def schedule_(self, X, **kw):
    out = schedule(self, X, **kw)
    if fault == "unassigned":
        out["assignments"].pop()
    if fault == "energy_altered":
        out["energy_j"] *= 1 + 1e-6
    return out


fj.FlatForestJax.__init__ = init_
fj.FlatForestJax.__call__ = call_
ClusterFrontend.schedule = schedule_
if fault == "device_dropped":
    MultiDeviceEngine.__init__ = engines_init_
sys.exit(server.main(sys.argv[1:], require_tpu=False))
"""


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A document with a schedule cell per objective, their mixes in a
    directory of their own."""
    mixes = tmp_path_factory.mktemp("mixes")
    for goal in OBJECTIVES:
        (mixes / f"{goal}.json").write_text(json.dumps(dict(MIX,
                                                            objective=goal)))
    doc = {"configs": [{"name": "pricing-2dev", "file": str(CONFIG)}],
           "workloads": [{"name": f"pricing-2dev.{goal}",
                          "config": "pricing-2dev", "traffic": goal,
                          "chips": 1} for goal in OBJECTIVES],
           "end_to_end": [
               {"name": "p95_ms", "unit": "ms"},
               {"name": "setup_s", "unit": "s"}],
           "per_layer": [{"name": "padded_row_share.batch", "unit": "%"}]}
    return doc, mixes


def one_run(bench, fault, trace=0, objective="energy", seed=4000000003):
    doc, mixes = bench
    args = run.parse(["--workload", f"pricing-2dev.{objective}",
                      "--seed", str(seed),
                      "--seconds", "1", "--trace", str(trace)])
    child = [sys.executable, "-c",
             CHILD.format(paths=[str(ROOT), str(ROOT / "src")], fault=fault)]
    saved, traffic.MIXES = traffic.MIXES, mixes
    try:
        return run.run(args, bench=doc, require_tpu=False, server_cmd=child)
    finally:
        traffic.MIXES = saved


@pytest.mark.parametrize("objective,trace", [
    ("energy", 0), ("energy", 1), ("makespan", 0)])
def test_sound_run_is_correct(bench, objective, trace):
    out = one_run(bench, None, trace, objective)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line["checks"]) == [
        "bad_assignments", "max_rel_err", "totals_rel_err", "objective_gap",
        "deadline_mismatch", "unanswered"]
    assert list(line)[-1] == "checks"
    want = {"padded_row_share.batch"} if trace else {"p95_ms", "setup_s"}
    assert set(line["metrics"]) == want
    assert out["check"]["rows"] > 0
    assert set(out["forests"]) == {e["column"] for e in
                                   json.loads(CONFIG.read_text())["forests"]}


@pytest.mark.parametrize("fault,caught_by", [
    ("forest_offset", "max_rel_err"),
    ("device_dropped", "objective_gap"),
    ("bf16_control", "max_rel_err"),
    ("unassigned", "bad_assignments"),
    ("energy_altered", "totals_rel_err")])
def test_broken_path_is_not_correct(bench, fault, caught_by):
    line = one_run(bench, fault)["line"]
    assert not line["correct"], line["checks"]
    check = line["checks"][caught_by]
    assert check["value"] > check["limit"], line["checks"]


def program_schedule(t_log, p_w, names, grids, objective, deadline_us):
    from repro.core.scheduler import DevicePredictor, schedule
    devices = [DevicePredictor(name, lambda X, j=j: t_log[:, j],
                               lambda X, j=j: p_w[:, j], freq_grid=grid)
               for j, (name, grid) in enumerate(zip(names, grids))]
    return schedule(np.zeros((len(t_log), 1), np.float32), devices,
                    objective, deadline_s=(None if deadline_us is None
                                           else deadline_us / 1e6))


@pytest.mark.parametrize("objective", ref_schedule.OBJECTIVES)
@pytest.mark.parametrize("deadline", [None, 0.5, 2.0],
                         ids=["none", "tight", "loose"])
def test_reference_makes_the_programs_schedule(objective, deadline):
    """On the same times and powers, the reference places every kernel on
    the same device at the same clock and start as the program does; the
    energy agrees to the order of its sum."""
    targets = json.loads((ROOT / "perfbench/data/suite_targets.json")
                         .read_text())
    names = list(targets["devices"])
    grids = [tuple(targets["devices"][d]["freq_grid"]) for d in names]
    cols = targets["columns"]
    rng = np.random.default_rng(7)
    for _ in range(6):
        idx = rng.integers(0, len(cols["tpu-v5e/time_us"]),
                           int(rng.integers(16, 96)))
        t_log = np.stack([np.asarray(cols[f"{d}/time_us"])[idx]
                          for d in names], axis=1)
        t_log *= 1 + 1e-3 * rng.standard_normal(t_log.shape)
        p_w = np.stack([np.asarray(cols[f"{d}/power_w"])[idx]
                        for d in names], axis=1)
        t_us = np.exp(t_log)
        deadline_us = (None if deadline is None
                       else deadline * t_us.min(axis=1).sum() / len(names))
        got = program_schedule(t_log, p_w, names, grids, objective,
                               deadline_us)
        want = ref_schedule.schedule(t_us, p_w, grids, objective,
                                     deadline_us)
        assert [(a.kernel, names.index(a.device), a.freq, a.t_us, a.power_w,
                 a.start_us) for a in got.assignments] == [
            (a["kernel"], a["device"], a["freq"], a["t_us"], a["power_w"],
             a["start_us"]) for a in want["assignments"]]
        assert got.makespan_us == want["makespan_us"]
        assert got.energy_j == pytest.approx(want["energy_j"], rel=1e-12)
        assert got.meets_deadline == want["meets_deadline"]


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "perfbench/schedule.py").read_text()
    assert "repro" not in source and "import jax" not in source
