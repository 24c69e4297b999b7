"""Whole runs of the harness on the CPU, with a small forest: a sound run
is correct, and a run whose timed path is broken underneath is not.

The server child here skips the look for a chip; everything else is the
run as the benchmark makes it, over loopback."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import run

ROOT = Path(run.__file__).resolve().parents[1]

CHILD = """
import sys
import time
sys.path[:0] = {paths!r}
import numpy as np
import jax.numpy as jnp
import repro.core.forest_jax as fj
import perfbench.server as server

fault = {fault!r}
sound = fj.FlatForestJax.__call__


def broken(self, x):
    x = jnp.asarray(x, jnp.float32)
    if fault == "state_unchanged":      # the walk never leaves the roots
        return fj._predict_flat_jax(*self.arrays, x, max_depth=0)
    if fault == "bf16_control":         # the walk in the precision below:
        f, thr, left, right, val, roots = self.arrays   # inputs, thresholds
        bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # leaves
        return fj._predict_flat_jax(f, bf(thr), left, right, bf(val), roots,
                                    bf(x), max_depth=self.max_depth)
    if fault == "half_trees":           # the mean over half of the trees
        *nodes, roots = self.arrays
        return fj._predict_flat_jax(*nodes, roots[:len(roots) // 2], x,
                                    max_depth=self.max_depth)
    if fault == "half_rows":            # a slow path, so one-row requests
        time.sleep(0.05)                # queue and merge into batches
    y = np.array(sound(self, x))
    if fault == "half_rows":            # half the batch gets the other's mean
        y[len(y) // 2:] = y[:max(len(y) // 2, 1)].mean()
    if fault == "altered_answer":       # one answer altered where made
        y[0] += 1e-3
    return y


if fault:
    fj.FlatForestJax.__call__ = broken
sys.exit(server.main(sys.argv[1:], require_tpu=False))
"""


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """BENCHMARK.json with both configurations cut to 16 trees of depth
    at most 8, served by flat-jax."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "perfbench/configs/et512-deep.json").read_text())
    cfg.update(n_estimators=16, max_depth=8)
    path = tmp_path_factory.mktemp("cfg") / "tiny.json"
    path.write_text(json.dumps(cfg))
    for c in doc["configs"]:
        c["file"] = str(path)
    return doc


def child(fault):
    return [sys.executable, "-c",
            CHILD.format(paths=[str(ROOT), str(ROOT / "src")], fault=fault)]


def one_run(bench, fault, workload="et512-deep.batch", trace=0):
    args = run.parse(["--workload", workload, "--seed", "4000000001",
                      "--seconds", "1", "--trace", str(trace)])
    return run.run(args, bench=bench, require_tpu=False,
                   server_cmd=child(fault))["line"]


@pytest.mark.parametrize("workload,trace", [
    ("et512-deep.batch", 0), ("et512-deep.single", 0),
    ("et512-deep.repeat", 0), ("et512-d10.batch", 1),
    ("et512-deep.single", 1), ("et512-deep.repeat", 1)])
def test_sound_run_is_correct(bench, workload, trace):
    line = one_run(bench, None, workload, trace)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = run.load_cell(bench, workload)
    want = cell.per_layer if trace else cell.end_to_end
    names = {m["name"] for m in want}
    if trace:       # no chip on the CPU: the device's metrics read nothing
        names = {n for n in names
                 if not n.startswith(("device_", "forest_"))}
    assert names == set(line["metrics"])
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_trees",
                                   "half_rows", "altered_answer",
                                   "bf16_control"])
def test_broken_path_is_not_correct(bench, fault):
    line = one_run(bench, fault)
    assert not line["correct"], line["checks"]
    assert line["checks"]["max_rel_err"]["value"] > run.MAX_REL_ERR


@pytest.mark.parametrize("workload", ["et512-deep.single",
                                      "et512-deep.repeat"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_trees",
                                   "half_rows", "altered_answer",
                                   "bf16_control"])
def test_broken_path_is_not_correct_in_one_row_cells(bench, fault, workload):
    """In ``repeat`` the cache hands back what the broken path made; half
    the batch left out needs merged calls, which its slow path makes."""
    line = one_run(bench, fault, workload)
    assert not line["correct"], line["checks"]
    assert line["checks"]["max_rel_err"]["value"] > run.MAX_REL_ERR


def test_no_chip_exits_nonzero_without_a_result(bench, capsys):
    rc = run.main(["--workload", "et512-deep.batch", "--seed", "1",
                   "--seconds", "1"], bench=bench)
    assert rc != 0
    assert "{" not in capsys.readouterr().out


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "et512-d10.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_load_process_initializes_no_jax_backend(bench, tmp_path):
    """The chip belongs to the server child: the load process, which
    imports the program's client, never starts a JAX backend."""
    (tmp_path / "bench.json").write_text(json.dumps(bench))
    code = f"""
import json, sys
sys.path[:0] = {[str(ROOT), str(ROOT / 'src')]!r}
from perfbench import run
bench = json.load(open({str(tmp_path / 'bench.json')!r}))
args = run.parse(["--workload", "et512-deep.single", "--seed", "9",
                  "--seconds", "1"])
out = run.run(args, bench=bench, require_tpu=False,
              server_cmd={child(None)!r})
from jax._src import xla_bridge
print("RESULT", out["line"]["correct"], xla_bridge.backends_are_initialized())
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-3:] == ["RESULT", "True", "False"]
