"""BENCHMARK.json keeps to its contract, and every part of every cell is
found from its own file by name."""
import tiny  # noqa: F401

import json
import math
import re

import pytest

from bench.harness import spec

BM = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys():
    assert list(BM) == ["command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"]
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells():
    t = BM["run_seconds"]
    assert 1 <= t <= 51 and t == int(t)
    runs = 2 + 14 * 24
    assert runs * (t + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lengths():
    names = [c["name"] for c in BM["configs"]] + CELLS + \
        [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in BM["configs"] + BM["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_entries_have_just_their_keys():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and c["reduced"] == []
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for cell in CELLS:
        mine = [m["name"] for m in spec.metrics_of(BM, cell, "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        layers = spec.metrics_of(BM, cell, "per_layer")
        assert layers
        for m in layers:       # what a layer moves, the cell reports
            assert m["moves"] in mine


def test_layers_spell_alike_and_metrics_name_real_cells():
    for m in BM["end_to_end"] + BM["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS
    by_layer = {}
    for m in BM["per_layer"]:
        by_layer.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_are_found_by_name(cell):
    w = spec.workload(BM, cell)
    cfg = spec.config(BM, w["config"])
    mix = spec.traffic(w["traffic"])
    assert mix["driver"] in ("engine", "scheduler")
    lim = spec.limits(cell)
    assert lim and all(v > 0 for v in lim.values())
    assert callable(spec.reference(cfg).logits_at)
    for kind in ("end_to_end", "per_layer"):
        for m in spec.metrics_of(BM, cell, kind):
            assert callable(spec.metric_reader(m["name"]))


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.workload(BM, "no.such_cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")


def test_configuration_files_are_json_with_their_source():
    for c in BM["configs"]:
        data = json.loads((spec.ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
        assert (spec.BENCH / "reference" / f"{data['reference']}.py").is_file()
        assert math.prod([data["hidden_size"], data["num_hidden_layers"]]) > 0
