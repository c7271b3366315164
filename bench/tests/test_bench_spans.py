"""The per-layer host-time shares read from the program's spans: each
reader on hand-built totals, and each cell's traced window at a tiny size
on the CPU."""
import sys

import pytest

from bench import harness
from bench.tests.tiny import ROOT, SEED, cell_config
from repro.obs import spans

SPEC = harness.load_benchmark(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]
SHARES = ["fleet.host_share", "intake.host_share", "engine.host_share",
          "backend.host_share", "backend.wait_share"]


def _t(self_s, total_s=None):
    return {"count": 1, "self_ns": int(self_s * 1e9),
            "total_ns": int((self_s if total_s is None else total_s) * 1e9)}


#: a 2 s window: fleet 0.6 s, intake 0.4 s, engine 0.3 s, backend code
#: 0.2 s, the wait on the device 0.1 s
TOTALS = {
    "fleet.run": _t(0.5, 2.0), "fleet.step": _t(0.1),
    "intake.transport": _t(0.3, 0.7), "intake.sweep": _t(0.1),
    "engine.generate": _t(0.1), "engine.assimilate": _t(0.15, 0.2),
    "engine.finish": _t(0.05),
    "backend.submit": _t(0.05), "backend.collect": _t(0.15, 0.25),
    "backend.wait": _t(0.1),
}
WANT = {"fleet.host_share": 30.0, "intake.host_share": 20.0,
        "engine.host_share": 15.0, "backend.host_share": 10.0,
        "backend.wait_share": 5.0}


@pytest.mark.parametrize("name", SHARES)
def test_share_on_hand_built_totals(name, monkeypatch):
    read = harness.metric_reader(name)
    monkeypatch.setattr(spans, "totals", lambda: TOTALS)
    assert read({"window_s": 2.0}) == pytest.approx(WANT[name])
    monkeypatch.setattr(spans, "totals", lambda: {})
    assert read({"window_s": 2.0}) is None


@pytest.mark.parametrize("name", SHARES)
def test_a_program_without_spans_reads_none(name, monkeypatch):
    # a program older than repro.obs.spans: the import fails, even with
    # totals that would read
    import repro.obs

    monkeypatch.setattr(spans, "totals", lambda: TOTALS)
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert harness.metric_reader(name)({"window_s": 2.0}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_window_accounts_for_the_host(cell, tmp_path):
    wl = harness.workload(SPEC, cell)
    want = [m["name"] for m in harness.cell_metrics(SPEC, cell, True)
            if m["name"] in SHARES]
    assert ("intake.host_share" in want) == (cell == "stripe79.served")
    session = harness.Session(cell_config(cell))
    try:
        spans.reset()
        w = session.window(harness.traffic_of(wl["traffic"]), SEED, 1.5,
                           tmp_path / "trace")
    finally:
        session.close()
    run = {"window_s": w["window_s"]}
    got = {name: harness.metric_reader(name)(run) for name in want}
    spans.reset()
    for name, v in got.items():
        assert v is not None and 0.0 <= v <= 100.0, (name, v)
    assert got["fleet.host_share"] > 0 and got["engine.host_share"] > 0
    assert sum(got.values()) <= 100.5, got
