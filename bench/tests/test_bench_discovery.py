"""A new configuration, traffic mix, driver and metric are files plus
entries in BENCHMARK.json, with no edit to a file that is there."""
import json
import shutil
import time

import pytest

from bench import harness
from bench.tests.tiny import ROOT, SEED, tiny

#: a way in that no cell uses: the batched grid without pipelining
SYNC_GRID = '''
import time

from bench.generator import SET_SEED, SearchLog, members, search_spec


class Driver:

    def __init__(self, problem, mix, seed, backend):
        self.problem, self.mix, self.seed = problem, mix, seed
        self.backend = backend
        self.searches = []

    def warm(self):
        spec = search_spec(self.problem, SET_SEED, 0)
        self.backend.warm(len(spec.x0), spec.grid.n_hosts)

    def run(self, deadline):
        from repro.core.substrates.batched_grid import BatchedVolunteerGrid
        order = members(self.mix, self.seed)
        while time.perf_counter() < deadline:
            spec = search_spec(self.problem, SET_SEED, next(order))
            log = SearchLog(spec.build_engine())
            self.searches.append(log)
            grid = BatchedVolunteerGrid(None, spec.grid, backend=self.backend,
                                        pipelined=False)
            grid.start(log.engine)
            while time.perf_counter() < deadline and grid.step():
                pass
            grid.finish()
            log.ended = log.engine.done
            log.hit = log.engine.best_fitness <= self.problem.target
        return {}
'''


def test_new_cell_from_files_alone(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    for d in ("configs", "traffic", "drivers"):
        (bench / d).mkdir()
    cfg = tiny(json.loads((ROOT / "bench/configs/stripe79.json").read_text()))
    cfg["name"] = "stripe79_b"
    cfg["stripe"]["seed"] = 791
    (bench / "configs/stripe79_b.json").write_text(json.dumps(cfg))
    (bench / "drivers/sync_grid.py").write_text(SYNC_GRID)
    (bench / "traffic/sync.json").write_text(json.dumps(
        {"driver": "sync_grid", "members": [0, 1, 2]}))
    (bench / "metrics/engine.searches_ended.py").write_text(
        "def read(run):\n"
        "    return sum(1 for s in run['searches'] if s['ended']) or None\n")
    spec = harness.load_benchmark(ROOT)
    spec["configs"].append({"name": "stripe79_b", "source": "x",
                            "file": "bench/configs/stripe79_b.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "stripe79_b.sync",
                              "config": "stripe79_b", "traffic": "sync",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "engine.searches_ended",
                              "unit": "searches", "better": "higher",
                              "source": "program_counter", "layer": "engine",
                              "moves": "eval_rate"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    line = harness.run_cell(tmp_path, "stripe79_b.sync", SEED, 1.5, False,
                            time.perf_counter(), require_tpu=False,
                            bench=bench, log=lambda m: None)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"eval_rate", "setup_s"}
    run = {"searches": [{"ended": True}, {"ended": False}]}
    assert harness.metric_reader("engine.searches_ended", bench)(run) == 1
    assert "engine.searches_ended" in {
        m["name"] for m in harness.cell_metrics(spec, "stripe79_b.sync",
                                                True)}


def test_a_driver_that_is_not_there_is_named():
    with pytest.raises(KeyError, match="no_such_driver"):
        harness.driver_class("no_such_driver")
