"""A run's last line holds the keys the contract fixes, in order, with
the numbers compared last; without a chip there is no result."""
import tiny

import json

import pytest

from bench import run as bench_run
from bench.harness import spec

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def cell():
    return tiny.run("engine")


def test_last_line_keys(cell):
    r = cell.result
    assert list(r) == KEYS
    json.dumps(r, allow_nan=False)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    want = {m["name"] for m in spec.metrics_of(
        spec.benchmark(), tiny.CELLS["engine"], "end_to_end")}
    assert set(r["metrics"]) == want
    assert all(set(v) == {"value", "unit"} for v in r["metrics"].values())
    assert set(r["checks"]) == set(spec.limits(tiny.CELLS["engine"]))
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_checks_are_the_last_stderr_lines(cell):
    assert cell.checks and all(x.startswith("check ") for x in cell.checks)
    assert any(x.startswith("compiles in window: 0") for x in cell.diag)
    assert any(x.startswith("longest token gaps") for x in cell.diag)


def test_no_accelerator_no_result(capsys):
    rc = bench_run.main(["--workload", tiny.CELLS["engine"], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "no accelerator" in out.err
