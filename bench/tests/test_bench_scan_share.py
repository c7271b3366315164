"""``intake.scan_share``, the self time of the work server's two scans of
the whole fleet (``intake.sweep``, ``intake.lapse``) over the window: the
reader on hand-built span totals, for a program without spans or without
the lapse span, and in the traced window of each cell that lists it, at
a tiny size on the CPU."""
import sys

import pytest

from bench import harness
from bench.tests.tiny import ROOT, SEED, cell_config
from repro.obs import spans

SPEC = harness.load_benchmark(ROOT)
METRIC = next(m for m in SPEC["per_layer"] if m["name"] == "intake.scan_share")


def _t(self_s, total_s=None, count=1):
    return {"count": count, "self_ns": int(self_s * 1e9),
            "total_ns": int((self_s if total_s is None else total_s) * 1e9)}


#: a 2 s window: the intake's message round trips hold both scans
TOTALS = {"intake.transport": _t(0.6, 0.9), "fleet.run": _t(0.5, 2.0),
          "backend.submit": _t(0.05)}


@pytest.mark.parametrize("sweep_s,lapse_s,want", [
    (0.2, 0.1, 15.0), (0.2, None, 10.0), (0.0, 0.04, 2.0), (None, None,
                                                           0.0)])
def test_scan_share_is_the_two_scans_self_time(sweep_s, lapse_s, want,
                                               monkeypatch):
    totals = dict(TOTALS)
    if sweep_s is not None:
        totals["intake.sweep"] = _t(sweep_s, count=7)
    if lapse_s is not None:
        totals["intake.lapse"] = _t(lapse_s, count=3)
    monkeypatch.setattr(spans, "totals", lambda: totals)
    read = harness.metric_reader("intake.scan_share")
    assert read({"window_s": 2.0}) == pytest.approx(want)
    # the intake's whole share still holds the scans
    assert harness.metric_reader("intake.host_share")(
        {"window_s": 2.0}) == pytest.approx(
        30.0 + 50.0 * ((sweep_s or 0.0) + (lapse_s or 0.0)))
    monkeypatch.setattr(spans, "totals", lambda: {})
    assert read({"window_s": 2.0}) is None


def test_a_program_without_spans_reads_none(monkeypatch):
    import repro.obs

    totals = dict(TOTALS, **{"intake.sweep": _t(0.1)})
    monkeypatch.setattr(spans, "totals", lambda: totals)
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert harness.metric_reader("intake.scan_share")(
        {"window_s": 2.0}) is None


@pytest.mark.parametrize("cell", METRIC["workloads"])
def test_traced_window_reads_the_scans(cell, tmp_path):
    wl = harness.workload(SPEC, cell)
    assert "intake.scan_share" in [
        m["name"] for m in harness.cell_metrics(SPEC, cell, True)]
    session = harness.Session(cell_config(cell))
    try:
        spans.reset()
        w = session.window(harness.traffic_of(wl["traffic"]), SEED, 1.5,
                           tmp_path / "trace")
    finally:
        session.close()
    tot = spans.totals()
    got = harness.metric_reader("intake.scan_share")(
        {"window_s": w["window_s"]})
    spans.reset()
    assert tot["intake.sweep"]["count"] > 0
    scans = sum(tot[n]["self_ns"] for n in ("intake.sweep", "intake.lapse")
                if n in tot)
    assert got == pytest.approx(100.0 * scans * 1e-9 / w["window_s"])
    assert 0 < got < 100.0
