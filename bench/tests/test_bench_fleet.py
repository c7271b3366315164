"""The ``fleet`` driver (``stripe86.portfolio``) at a tiny size on the CPU:
each window's searches commit what their specs commit alone, a search
leaves the rounds once it reaches the target, a portfolio starts only
after the last one's searches have all ended, and nothing compiles in the
window; and the orchestrator's two readers on hand-built totals."""
import sys

import pytest

from bench import harness
from bench.generator import members
from bench.tests.tiny import SEED, cell_config
from repro.core.engine import identical_trajectories
from repro.core.orchestrator import FleetScheduler
from repro.obs import spans

CELL = "stripe86.portfolio"


@pytest.fixture(scope="module")
def window():
    """One 5 s window of the cell at the tiny size, 3 iterations a search
    (so that some searches stop short of the target), with every round's
    searches and every admission recorded in order; and the solo run of
    each search of the window's first two portfolios."""
    cfg = cell_config(CELL)
    cfg["anm"]["max_iterations"] = 3
    mix = harness.traffic_of("portfolio")
    events = []
    round_, admit = FleetScheduler.round, FleetScheduler.admit

    def recorded_round(self, live):
        events.append(("round", [(ls.engine, ls.engine.best_fitness)
                                 for ls in live]))
        return round_(self, live)

    def recorded_admit(self, spec, search_id, *args, **kw):
        ls = admit(self, spec, search_id, *args, **kw)
        events.append(("admit", ls.engine))
        return ls

    session = harness.Session(cfg)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(FleetScheduler, "round", recorded_round)
            mp.setattr(FleetScheduler, "admit", recorded_admit)
            w = session.window(mix, SEED, 5.0)
        driver = w["driver"]
        n = mix["searches"]
        order = members(mix, SEED)
        solo = []
        for k in range(2):
            _, specs = driver.portfolio(next(order))
            solo.append([s.solo_run(session.problem.backend) for s in specs])
    finally:
        session.close()
    logs = driver.searches
    portfolios = [logs[i:i + n] for i in range(0, len(logs), n)]
    return {"w": w, "events": events, "portfolios": portfolios,
            "solo": solo, "target": session.problem.target, "n": n}


def test_window_searches_commit_what_they_commit_alone(window):
    assert len(window["portfolios"]) >= 2
    committed = 0
    for logs, solos in zip(window["portfolios"], window["solo"]):
        assert len(logs) == len(solos) == window["n"]
        for log, solo in zip(logs, solos):
            got = log.engine
            committed += got.iteration
            if log.ended and not log.hit:
                # stopped after its iterations: the whole run and its
                # counters are the solo run's
                assert identical_trajectories(got, solo)
                assert got.stats == solo.stats
                continue
            # retired at the target (or cut by the window): a prefix
            assert got.iteration <= solo.iteration
            for a, b in zip(got.history, solo.history):
                assert (a.center == b.center).all()
                assert a.best_fitness == b.best_fitness
    assert committed > 0


def test_a_search_leaves_the_rounds_once_it_hits_the_target(window):
    target = window["target"]
    hits = [log for logs in window["portfolios"] for log in logs
            if log.hit]
    assert hits
    stepped = {}
    for kind, arg in window["events"]:
        if kind == "round":
            for engine, best in arg:
                # only a search still short of the target is stepped
                assert best > target
                stepped[id(engine)] = stepped.get(id(engine), 0) + 1
    for log in hits:
        assert log.ended and log.engine.best_fitness <= target
        assert stepped[id(log.engine)] >= 1


def test_a_portfolio_starts_after_all_of_the_last_have_ended(window):
    portfolios = window["portfolios"]
    first_admit = {}
    last_round = {}
    for i, (kind, arg) in enumerate(window["events"]):
        if kind == "admit":
            first_admit.setdefault(id(arg), i)
        else:
            for engine, _ in arg:
                last_round[id(engine)] = i
    for done, after in zip(portfolios, portfolios[1:]):
        assert all(log.ended for log in done)
        start = min(first_admit[id(log.engine)] for log in after)
        assert max(last_round[id(log.engine)] for log in done) < start
        # and the whole next portfolio is admitted before its first round
        assert max(first_admit[id(log.engine)] for log in after) < \
            min(last_round.get(id(log.engine), len(window["events"]))
                for log in after)


def test_the_window_compiles_nothing(window):
    w = window["w"]
    assert w["compiles"] == 0
    assert len(w["ks"]) > 0 and w["lanes"] > 0


def test_a_whole_portfolio_is_bit_identical_to_its_solo_runs():
    from bench.drivers import fleet

    cfg = cell_config(CELL)
    cfg["anm"]["max_iterations"] = 2
    mix = harness.traffic_of("portfolio")
    session = harness.Session(cfg)
    try:
        driver = fleet.Driver(session.problem, mix, SEED,
                              session.problem.backend)
        driver.warm()
        rows = fleet.solo_parity(driver, mix["members"][1])
    finally:
        session.close()
    assert len(rows) == mix["searches"]
    for row in rows:
        assert row["history"] and row["stats"], row
        assert row["first_diff"] is None and row["max_rel_diff"] == 0.0
        assert row["iterations"][0] == row["iterations"][1] == 2


def _t(count, self_s):
    return {"count": count, "self_ns": int(self_s * 1e9),
            "total_ns": int(self_s * 1e9)}


#: a 2 s window: the orchestrator's rounds 0.1 s of self time, its
#: dispatches (30 whole, 10 forced) 0.2 s, its collects 0.1 s
TOTALS = {"orchestrator.round": _t(40, 0.1),
          "orchestrator.flush": _t(30, 0.15),
          "orchestrator.forced": _t(10, 0.05),
          "orchestrator.collect": _t(80, 0.1),
          "fleet.step": _t(320, 0.6), "backend.submit": _t(40, 0.2)}
WANT = {"orchestrator.host_share": 20.0, "orchestrator.forced_share": 25.0}


@pytest.mark.parametrize("name", sorted(WANT))
def test_orchestrator_reader_on_hand_built_totals(name, monkeypatch):
    read = harness.metric_reader(name)
    monkeypatch.setattr(spans, "totals", lambda: TOTALS)
    assert read({"window_s": 2.0}) == pytest.approx(WANT[name])
    # a program with spans but none of the orchestrator's (the parent of
    # these spans, or a cell that bypasses the orchestrator)
    others = {k: v for k, v in TOTALS.items()
              if not k.startswith("orchestrator.")}
    monkeypatch.setattr(spans, "totals", lambda: others)
    assert read({"window_s": 2.0}) is None
    monkeypatch.setattr(spans, "totals", lambda: {})
    assert read({"window_s": 2.0}) is None


def test_forced_share_reads_zero_when_no_dispatch_was_forced(monkeypatch):
    read = harness.metric_reader("orchestrator.forced_share")
    monkeypatch.setattr(spans, "totals",
                        lambda: {"orchestrator.flush": _t(5, 0.01)})
    assert read({"window_s": 2.0}) == 0.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_orchestrator_reader_without_spans_reads_none(name, monkeypatch):
    import repro.obs

    monkeypatch.setattr(spans, "totals", lambda: TOTALS)
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert harness.metric_reader(name)({"window_s": 2.0}) is None
