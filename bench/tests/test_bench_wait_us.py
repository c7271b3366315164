"""``backend.wait_us``, the mean time the host spends blocked in one of the
backend's collects: the reader on hand-built span totals, for a program
without spans, and in each cell's traced window at a tiny size on the
CPU."""
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


#: the backend's spans of a 2 s window, without its wait
TOTALS = {"backend.submit": _t(0.05), "backend.collect": _t(0.15, 0.25)}


@pytest.mark.parametrize("count,total_s,want_us", [
    (1, 0.1, 100_000.0), (4, 0.1, 25_000.0), (3, 0.0015, 500.0),
    (0, 0.0, None)])
def test_wait_us_is_the_mean_wait_a_collect(count, total_s, want_us,
                                            monkeypatch):
    totals = dict(TOTALS, **{"backend.wait": _t(total_s, count=count)})
    monkeypatch.setattr(spans, "totals", lambda: totals)
    got = harness.metric_reader("backend.wait_us")({"window_s": 2.0})
    if want_us is None:
        assert got is None
    else:
        assert got == pytest.approx(want_us)
    # the share beside it still reads the wait's whole total
    assert harness.metric_reader("backend.wait_share")(
        {"window_s": 2.0}) == pytest.approx(100.0 * total_s / 2.0)
    monkeypatch.setattr(spans, "totals", lambda: {})
    assert harness.metric_reader("backend.wait_us")({"window_s": 2.0}) is None


def test_a_program_without_spans_reads_none(monkeypatch):
    # a program older than repro.obs.spans: the import fails, even with
    # totals that would read
    import repro.obs

    totals = dict(TOTALS, **{"backend.wait": _t(0.1)})
    monkeypatch.setattr(spans, "totals", lambda: totals)
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert harness.metric_reader("backend.wait_us")({"window_s": 2.0}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_window_reads_a_wait(cell, tmp_path):
    wl = harness.workload(SPEC, cell)
    assert "backend.wait_us" in [
        m["name"] for m in harness.cell_metrics(SPEC, cell, True)]
    session = harness.Session(cell_config(cell))
    try:
        spans.reset()
        w = session.window(harness.traffic_of(wl["traffic"]), SEED, 1.5,
                           tmp_path / "trace")
    finally:
        session.close()
    wait = spans.totals().get("backend.wait", {})
    got = harness.metric_reader("backend.wait_us")({"window_s": w["window_s"]})
    spans.reset()
    assert wait.get("count", 0) > 0
    assert got == pytest.approx(wait["total_ns"] / wait["count"] / 1000.0)
    assert 0 < got < 1e6 * w["window_s"]
