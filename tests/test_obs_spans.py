"""Program spans on the profiler's clock (``repro.obs.spans``): nothing is
recorded while the profiler is off; nested spans split their time into
self and child time, per thread, with their own bookkeeping apart; the
served loop records one span a message; the core layers import the
spans without the operator plane; and a traced window carries the spans
on the host plane beside the device's operations."""
import os
import pathlib
import subprocess
import sys
import threading
import time

import jax
import pytest

from repro.obs import spans

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402


@pytest.fixture
def on(monkeypatch):
    """Spans record as if the profiler were tracing (the ``TraceMe`` they
    enter is itself a no-op without one)."""
    spans.reset()
    monkeypatch.setattr(spans, "enabled", lambda: True)
    yield
    spans.reset()


def test_nothing_is_recorded_while_the_profiler_is_off():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    spans.reset()

    @spans.spanned("t.decorated")
    def f(x, *, y=1):
        return x + y

    with spans.span("t.block") as s:
        assert f(1, y=2) == 3
    assert s is spans.span("t.other")          # the one shared no-op
    assert spans.totals() == {}


def test_nested_spans_split_self_and_child_time(on):
    @spans.spanned("t.inner")
    def inner(dt):
        time.sleep(dt)
        return dt

    with spans.span("t.outer"):
        assert inner(0.02) == 0.02
        with spans.span("t.inner"):
            time.sleep(0.01)
        time.sleep(0.01)
    tot = spans.totals()
    out, inn = tot["t.outer"], tot["t.inner"]
    assert out["count"] == 1 and inn["count"] == 2
    assert inn["self_ns"] == inn["total_ns"] >= 30_000_000
    # the outer span's self time is its duration less its children's
    # whole cost: their time and their bookkeeping
    assert out["self_ns"] == \
        out["total_ns"] - inn["total_ns"] - inn["overhead_ns"]
    assert out["self_ns"] >= 10_000_000
    assert out["overhead_ns"] > 0 and inn["overhead_ns"] > 0
    # a span of the same name inside itself: self times still add up
    with spans.span("t.outer"):
        with spans.span("t.outer"):
            pass
    tot = spans.totals()["t.outer"]
    assert tot["count"] == 3 and 0 <= tot["self_ns"] <= tot["total_ns"]


def test_a_span_closes_on_an_exception(on):
    with pytest.raises(ValueError):
        with spans.span("t.outer"):
            with spans.span("t.inner"):
                raise ValueError
    with spans.span("t.after"):
        pass
    tot = spans.totals()
    assert tot["t.inner"]["count"] == 1
    assert tot["t.after"]["self_ns"] == tot["t.after"]["total_ns"]


def test_stacks_are_per_thread(on):
    outer_open, inner_done = threading.Event(), threading.Event()

    def a():
        with spans.span("t.a"):
            outer_open.set()
            assert inner_done.wait(10)

    def b():
        assert outer_open.wait(10)
        with spans.span("t.b"):
            time.sleep(0.03)
        inner_done.set()

    threads = [threading.Thread(target=a), threading.Thread(target=b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    tot = spans.totals()
    # b's span ran inside a's on the clock, but on another thread: it is
    # no child of a's
    assert tot["t.a"]["self_ns"] == tot["t.a"]["total_ns"] >= 30_000_000
    assert tot["t.b"]["self_ns"] == tot["t.b"]["total_ns"]


def test_many_threads_lose_no_count(on):
    n_threads, n_spans = 8, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_spans):
                with spans.span("t.outer"):
                    with spans.span("t.inner"):
                        pass
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    tot = spans.totals()
    assert tot["t.outer"]["count"] == tot["t.inner"]["count"] == \
        n_threads * n_spans
    assert tot["t.outer"]["self_ns"] == tot["t.outer"]["total_ns"] \
        - tot["t.inner"]["total_ns"] - tot["t.inner"]["overhead_ns"]


def test_reset_forgets_and_totals_are_a_copy(on):
    with spans.span("t.x"):
        pass
    copy = spans.totals()
    copy["t.x"]["count"] = 99
    assert spans.totals()["t.x"]["count"] == 1
    spans.reset()
    assert spans.totals() == {}
    with spans.span("t.x"):
        pass
    assert spans.totals()["t.x"]["count"] == 1


def test_spans_land_on_the_host_plane_of_the_trace(tmp_path):
    import jax.numpy as jnp

    spans.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # as the benchmark traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert jax.profiler.TraceAnnotation.is_enabled()
        with spans.span("fleet.step"):
            with spans.span("backend.wait"):
                jnp.arange(64.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    tot = spans.totals()
    spans.reset()
    assert tot["fleet.step"]["count"] == tot["backend.wait"]["count"] == 1
    tr = trace_reduce.load(tmp_path)
    host = {ev[0] for p in tr["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]}
    assert {"fleet.step", "backend.wait"} <= host


def test_served_loop_records_one_intake_span_a_message(on, monkeypatch):
    """The served path's one per-message span sits in the client pool's
    call of its connection, around the codec and ``WorkServer.handle``;
    the search it watches is the one it would be unwatched."""
    import numpy as np

    from repro.core.substrates.eval_backend import InProcessEvalBackend
    from repro.server.sim import ServerSubstrate, smoke_problem

    spec, fleet, f_batch = smoke_problem(n_stars=64, n_hosts=16, m=8,
                                         iterations=1)
    backend = InProcessEvalBackend(f_batch, n_dims=8, max_bucket=16)
    res = ServerSubstrate(spec, fleet, backend, warm=False).run()
    tot = spans.totals()
    assert tot["intake.transport"]["count"] == res.pool.messages
    assert tot["fleet.run"]["count"] == 1
    assert {"intake.sweep", "engine.generate", "engine.assimilate"} <= \
        set(tot)
    for name, t in tot.items():
        assert 0 <= t["self_ns"] <= t["total_ns"], name
    spans.reset()
    plain = ServerSubstrate(spec, fleet, backend, warm=False)
    monkeypatch.setattr(spans, "enabled", lambda: False)
    res_off = plain.run()
    assert spans.totals() == {}
    assert np.array_equal(res.engines[0].center, res_off.engines[0].center)
    assert res.engines[0].best_fitness == res_off.engines[0].best_fitness


def test_core_layers_import_spans_without_the_operator_plane():
    code = ("import sys, repro.core.engine, "
            "repro.core.substrates.batched_grid; "
            "print(*sorted(m for m in sys.modules "
            "if m.startswith(('repro.obs', 'repro.server'))))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu",
                              "PYTHONPATH": str(ROOT / "src")}).stdout
    loaded = set(out.split())
    assert {m for m in loaded if m.startswith("repro.obs")} == \
        {"repro.obs", "repro.obs.spans"}
    assert "repro.server.protocol" not in loaded


def _quadratic_backend(n=8):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.substrates.eval_backend import InProcessEvalBackend

    x_opt = jnp.asarray(np.linspace(-0.5, 0.5, n, dtype=np.float32))

    @jax.jit
    def f_batch(xs):
        return jnp.sum((xs - x_opt[None, :]) ** 2, axis=1)

    return InProcessEvalBackend(f_batch, n_dims=n, max_bucket=64)


class _SleepyGrid:
    """A search's grid whose tick is one ``fleet.step`` span of 5 ms."""

    def step(self):
        with spans.span("fleet.step"):
            time.sleep(0.005)
        return True


def test_a_round_s_self_time_leaves_out_the_fleet_ticks(on):
    from types import SimpleNamespace

    from repro.core.grid import GridConfig
    from repro.core.orchestrator import FleetScheduler

    sched = FleetScheduler(_quadratic_backend(), GridConfig(n_hosts=64))
    live = [SimpleNamespace(grid=_SleepyGrid()) for _ in range(3)]
    assert sched.round(live) == []
    tot = spans.totals()
    rnd, tick = tot["orchestrator.round"], tot["fleet.step"]
    assert rnd["count"] == 1 and tick["count"] == 3
    assert tick["total_ns"] >= 15_000_000
    assert rnd["self_ns"] == \
        rnd["total_ns"] - tick["total_ns"] - tick["overhead_ns"]
    assert rnd["self_ns"] < tick["total_ns"]
    # nothing was submitted: the round's flush dispatched nothing
    assert "orchestrator.flush" not in tot


def test_a_flush_is_counted_once_a_dispatched_round(on):
    import numpy as np

    from repro.core.orchestrator import CoalescingSubmitter

    co = CoalescingSubmitter(_quadratic_backend())
    co.flush()                                   # empty: no span
    assert spans.totals() == {}
    rng = np.random.default_rng(0)
    lanes = [co.submit(tag, rng.normal(size=(k, 8)))
             for tag, k in ((0, 3), (1, 5))]
    co.flush()
    co.flush()                                   # empty again
    tot = spans.totals()
    assert tot["orchestrator.flush"]["count"] == 1
    assert "orchestrator.forced" not in tot
    assert co.stats.dispatches == 1
    assert [len(co.collect(lane)) for lane in lanes] == [3, 5]
    tot = spans.totals()
    assert tot["orchestrator.collect"]["count"] == 2
    assert tot["orchestrator.flush"]["count"] == 1
    # the backend's spans nest in the coalescer's, and stay theirs
    assert tot["backend.submit"]["count"] == 1
    assert tot["backend.collect"]["count"] == 1


def test_a_collect_on_the_open_round_counts_one_forced_dispatch(on):
    import numpy as np

    from repro.core.orchestrator import CoalescingSubmitter

    co = CoalescingSubmitter(_quadratic_backend())
    rng = np.random.default_rng(1)
    first = co.submit(0, rng.normal(size=(4, 8)))
    second = co.submit(1, rng.normal(size=(2, 8)))
    assert len(co.collect(first)) == 4           # forces the open round
    assert len(co.collect(second)) == 2          # already dispatched
    co.flush()                                   # nothing left open
    tot = spans.totals()
    assert tot["orchestrator.forced"]["count"] == 1
    assert tot["orchestrator.collect"]["count"] == 2
    assert "orchestrator.flush" not in tot
    assert co.stats.forced_flushes == 1 and co.stats.dispatches == 1
    forced = tot["orchestrator.forced"]
    assert 0 <= forced["self_ns"] <= forced["total_ns"]


def test_a_traced_portfolio_counts_its_dispatches_and_commits_as_untraced(
        on, monkeypatch):
    """Over a small coalesced portfolio the spans count the coalescer's
    dispatches, whole and forced, and the searches commit what they
    commit with the spans off."""
    import numpy as np

    from repro.core.anm import AnmConfig
    from repro.core.engine import identical_trajectories
    from repro.core.grid import GridConfig
    from repro.core.orchestrator import (FleetScheduler, SearchDirector,
                                         multi_start_specs)

    def portfolio():
        sched = FleetScheduler(_quadratic_backend(),
                               GridConfig(n_hosts=128, failure_prob=0.1,
                                          malicious_prob=0.02, seed=3))
        anm = AnmConfig(m_regression=24, m_line_search=24, max_iterations=2)
        specs = multi_start_specs(sched, np.ones(8), -5 * np.ones(8),
                                  5 * np.ones(8), 0.5 * np.ones(8), anm, 4,
                                  seed=0, jitter=0.3)
        return SearchDirector(sched, specs).run(), sched

    traced, sched = portfolio()
    tot = spans.totals()
    st = sched.coalescer.stats
    assert st.forced_flushes > 0
    assert tot["orchestrator.forced"]["count"] == st.forced_flushes
    assert tot["orchestrator.flush"]["count"] == \
        st.dispatches - st.forced_flushes
    assert tot["orchestrator.round"]["count"] == traced.rounds
    for name in ("orchestrator.round", "orchestrator.flush",
                 "orchestrator.forced", "orchestrator.collect"):
        assert 0 <= tot[name]["self_ns"] <= tot[name]["total_ns"], name
    spans.reset()
    monkeypatch.setattr(spans, "enabled", lambda: False)
    plain, _ = portfolio()
    assert spans.totals() == {}
    for a, b in zip(traced.outcomes, plain.outcomes):
        assert identical_trajectories(a.engine, b.engine)
        assert a.engine.stats == b.engine.stats
