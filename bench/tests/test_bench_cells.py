"""Each cell's traffic at a tiny size on the CPU, through the harness."""
import json

import pytest

from bench import harness, run
from bench.tests.tiny import ROOT, SEED, run_tiny

SPEC = harness.load_benchmark(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    line = run_tiny(cell)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"], line["checks"]
    assert line["checks"]["compiles"] == {"value": 0, "limit": 0}
    want = {m["name"] for m in harness.cell_metrics(SPEC, cell, False)}
    assert set(line["metrics"]) == want
    for name, m in line["metrics"].items():
        assert m["value"] > 0, name
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 1
    json.dumps(line, allow_nan=False)


def test_every_metric_has_a_reader_and_every_cell_its_metrics():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for cell in CELLS:
        e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell, False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(SPEC, cell, True)


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out == ""
