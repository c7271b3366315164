"""``backend.submit_us``, the mean self time of one of the backend's
submits (staging, dispatch, the start of the read-back): the reader on
hand-built span totals, for a program without spans, and in each cell's
traced window at a tiny size on the CPU."""
import sys

import pytest

from bench import harness
from bench.tests.tiny import ROOT, SEED, cell_config
from repro.obs import spans

SPEC = harness.load_benchmark(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _t(self_s, total_s=None, count=1):
    return {"count": count, "self_ns": int(self_s * 1e9),
            "total_ns": int((self_s if total_s is None else total_s) * 1e9)}


#: the backend's other spans of a 2 s window
TOTALS = {"backend.collect": _t(0.15, 0.25), "backend.wait": _t(0.1)}


@pytest.mark.parametrize("count,self_s,total_s,want_us", [
    (1, 0.1, 0.1, 100_000.0), (4, 0.1, 0.1, 25_000.0),
    (1000, 0.3, 0.3, 300.0), (8, 0.002, 0.01, 250.0), (0, 0.0, 0.0, None)])
def test_submit_us_is_the_mean_self_time_a_submit(count, self_s, total_s,
                                                  want_us, monkeypatch):
    totals = dict(TOTALS, **{"backend.submit": _t(self_s, total_s, count)})
    monkeypatch.setattr(spans, "totals", lambda: totals)
    got = harness.metric_reader("backend.submit_us")({"window_s": 2.0})
    if want_us is None:
        assert got is None
    else:
        assert got == pytest.approx(want_us)
    # the backend's share beside it still reads the submit's whole self time
    assert harness.metric_reader("backend.host_share")(
        {"window_s": 2.0}) == pytest.approx(100.0 * (self_s + 0.15) / 2.0)
    monkeypatch.setattr(spans, "totals", lambda: {})
    assert harness.metric_reader("backend.submit_us")(
        {"window_s": 2.0}) is None


def test_a_program_without_spans_reads_none(monkeypatch):
    # a program older than repro.obs.spans: the import fails, even with
    # totals that would read
    import repro.obs

    totals = dict(TOTALS, **{"backend.submit": _t(0.1)})
    monkeypatch.setattr(spans, "totals", lambda: totals)
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert harness.metric_reader("backend.submit_us")(
        {"window_s": 2.0}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_window_reads_a_submit(cell, tmp_path):
    wl = harness.workload(SPEC, cell)
    assert "backend.submit_us" in [
        m["name"] for m in harness.cell_metrics(SPEC, cell, True)]
    session = harness.Session(cell_config(cell))
    try:
        spans.reset()
        w = session.window(harness.traffic_of(wl["traffic"]), SEED, 1.5,
                           tmp_path / "trace")
    finally:
        session.close()
    submit = spans.totals().get("backend.submit", {})
    got = harness.metric_reader("backend.submit_us")(
        {"window_s": w["window_s"]})
    spans.reset()
    assert submit.get("count", 0) > 0
    assert got == pytest.approx(submit["self_ns"] / submit["count"] / 1000.0)
    assert 0 < got < 1e6 * w["window_s"]
