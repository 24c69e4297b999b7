"""BENCHMARK.json and the files it names agree: every cell, configuration,
mix and metric is found by its name."""
import json
import re
from pathlib import Path

import pytest

from perfbench import readings, run, traffic

ROOT = Path(run.__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    assert NAME.match(m["name"])
    assert callable(readings.reader(m["name"]))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_reports_enough(w):
    cell = run.load_cell(BENCH, w["name"])
    assert cell.config["name"] == w["config"]
    assert traffic.load_mix(w["traffic"])["loop"] in ("open", "closed")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_names_are_unique_and_layers_named():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    for m in BENCH["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
