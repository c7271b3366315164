"""The feeder throttle's live-work count (``FgdoAnmServer._live_count``).

The served ``request_work`` path holds outstanding current-phase work under
``wanted() × overcommit``.  The count it compares is kept incrementally, as
derived state; these tests hold it to the full recount it replaced: the
same count at every decision of a served run, the same trajectory, tables
and counters end to end, and the same decisions under a clock that runs
backwards.
"""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anm import AnmConfig
from repro.core.engine import identical_trajectories
from repro.core.fgdo import FgdoAnmServer, WorkUnit
from repro.core.grid import GridConfig
from repro.core.orchestrator.director import SearchSpec
from repro.core.substrates.eval_backend import InProcessEvalBackend
from repro.server import protocol
from repro.server import server as server_mod
from repro.server.checkpoint import from_jsonable, to_jsonable
from repro.server.sim import SimClientPool
from repro.server.transport import LoopbackTransport

pytestmark = pytest.mark.server

N_DIMS = 4

# two fleets, each tight enough (lease 2x and reissue timeout 0.75x the base
# evaluation time, against a lognormal speed spread) that leases lapse,
# vanished hosts abandon theirs by re-requesting, and slow workunits age
# out of the live count; the second also lies often enough to reject
# candidates in validation
FLEETS = {
    "churn": GridConfig(n_hosts=32, speed_sigma=1.0, failure_prob=0.3,
                        malicious_prob=0.05, seed=5),
    "hostile": GridConfig(n_hosts=40, speed_sigma=0.8, failure_prob=0.15,
                          malicious_prob=0.25, seed=9),
}
LEASE, REISSUE = 2.0, 0.75


class RecountFeeder(FgdoAnmServer):
    """The feeder as it was before the incremental count: every request
    outside validation prunes finished phases and recounts the table."""

    refused_by_cap = 0

    def generate_work(self, host_id, now):
        eng = self.engine
        if eng.done or eng.validating:
            return super().generate_work(host_id, now)   # counts nothing
        if eng.phase == "bootstrap":
            live = sum(1 for wu in self.outstanding.values()
                       if wu.phase_id == eng.phase_id and
                       now - wu.issued_at <= self.val_reissue_timeout)
            if live >= 2:
                return None
        if self.overcommit is not None:
            for wid in [wid for wid, wu in self.outstanding.items()
                        if wu.phase_id != eng.phase_id]:
                del self.outstanding[wid]
            live = sum(1 for wu in self.outstanding.values()
                       if now - wu.issued_at <= self.val_reissue_timeout)
            if live >= int(np.ceil(eng.wanted() * self.overcommit)):
                self.refused_by_cap += 1
                return None
        reqs = eng.generate(1)
        if not reqs:
            return None
        req = reqs[0]
        wu = WorkUnit(req.ticket, req.phase_id, np.asarray(req.point),
                      req.alpha, req.validates, issued_at=now)
        self.outstanding[wu.wu_id] = wu
        self.registry.on_issue(host_id, now)
        return wu


class CheckedFeeder(FgdoAnmServer):
    """The incremental feeder, checked against a recount of the table at
    every count it makes; records what each count saw."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.checks = []              # (phase, phase_id, live, in_phase)
        self.requests = 0
        self.behind = 0               # counts on a clock that ran backwards

    def generate_work(self, host_id, now):
        self.requests += 1
        return super().generate_work(host_id, now)

    def _live_count(self, now):
        self.behind += now < self._live_now
        got = super()._live_count(now)
        eng = self.engine
        in_phase = [wu for wu in self.outstanding.values()
                    if wu.phase_id == eng.phase_id]
        want = sum(1 for wu in in_phase
                   if now - wu.issued_at <= self.val_reissue_timeout)
        assert got == want, (eng.phase, eng.phase_id, now, got, want)
        self.checks.append((eng.phase, eng.phase_id, want, len(in_phase)))
        return got


def _quad_fitness(n=N_DIMS, seed=3):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, n)).astype(np.float32)
    H = jnp.asarray(A @ A.T + n * np.eye(n, dtype=np.float32))
    x_opt = jnp.asarray(rng.uniform(-0.5, 0.5, n).astype(np.float32))

    @jax.jit
    def f_batch(xs):
        d = xs - x_opt[None, :]
        return 0.5 * jnp.einsum("mi,ij,mj->m", d, H, d)

    return f_batch


@pytest.fixture(scope="module")
def backend():
    return InProcessEvalBackend(_quad_fitness(), n_dims=N_DIMS,
                                max_bucket=64)


def _spec(fleet, m=8, iterations=3):
    return SearchSpec(
        name="feeder", x0=np.full(N_DIMS, 1.0), lo=np.full(N_DIMS, -10.0),
        hi=np.full(N_DIMS, 10.0), step=np.full(N_DIMS, 0.5),
        anm=AnmConfig(m_regression=m, m_line_search=m,
                      max_iterations=iterations),
        grid=fleet, engine_seed=11)


def _state_digest(srv):
    doc = json.dumps(to_jsonable(srv.state_dict()), sort_keys=True)
    return hashlib.sha256(doc.encode()).hexdigest()


def _mid_phase(srv):
    """A regression or line-search phase with work in flight and results
    already in: the point where a restore has live work to rebuild."""
    f = srv.searches[0].fgdo
    eng = f.engine
    return (eng.phase in ("regression", "linesearch")
            and eng.iteration >= 1 and eng._res_count > 0
            and len(f.outstanding) > 0)


def _serve(monkeypatch, backend, fleet_name, feeder, *, roundtrip_after=None,
           digest_every=None):
    """Serve one search over loopback to the simulated fleet with
    ``feeder`` as the adapter class.  With ``roundtrip_after`` the server
    is replaced, at the first mid-phase message boundary past that many
    messages, by a fresh one loaded from its ``state_dict``."""
    monkeypatch.setattr(server_mod, "FgdoAnmServer", feeder)
    fleet = FLEETS[fleet_name]
    spec = _spec(fleet)

    def build():
        return server_mod.WorkServer(
            [spec], lease_timeout=LEASE * fleet.base_eval_time,
            idle_retry=fleet.idle_retry,
            val_reissue_timeout=REISSUE * fleet.base_eval_time)

    servers = [build()]
    seen = {"messages": 0, "roundtrip_at": None}
    digests = []

    def handler(msg):
        n = seen["messages"] = seen["messages"] + 1
        srv = servers[-1]
        if (roundtrip_after is not None and seen["roundtrip_at"] is None
                and n > roundtrip_after and _mid_phase(srv)):
            state = from_jsonable(json.loads(json.dumps(
                to_jsonable(srv.state_dict()))))
            srv = build()
            srv.load_state(state)
            servers.append(srv)
            seen["roundtrip_at"] = n
        rep = srv.handle(msg)
        if digest_every and n % digest_every == 0:
            digests.append(_state_digest(srv))
        return rep

    transport = LoopbackTransport().start(handler)
    conn = transport.connect()
    try:
        pool = SimClientPool(fleet, backend).run(conn)
    finally:
        conn.close()
        transport.stop()
    return servers, pool, seen, digests


@pytest.mark.parametrize("fleet_name", sorted(FLEETS))
def test_incremental_count_matches_recount(monkeypatch, backend, fleet_name):
    """Every count the feeder makes in a served run equals a recount of
    the outstanding table, across the bootstrap probe, phase flips,
    validation rounds, lapsed and abandoned leases, aged-out work and a
    ``state_dict``/``load_state`` round trip in mid-phase."""
    servers, pool, seen, _ = _serve(monkeypatch, backend, fleet_name,
                                    CheckedFeeder, roundtrip_after=200)
    feeders = [s.searches[0].fgdo for s in servers]
    checks = [c for f in feeders for c in f.checks]
    last = servers[-1]
    eng = last.searches[0].fgdo.engine

    assert eng.done
    assert len(servers) == 2 and seen["roundtrip_at"] is not None
    # the restored feeder rebuilt its index and went on counting
    assert feeders[1].checks and feeders[1].requests > 0
    phases = {p for p, _, _, _ in checks}
    assert {"bootstrap", "regression", "linesearch"} <= phases
    assert len({pid for _, pid, _, _ in checks}) >= 2 * eng.iteration
    assert eng.stats.validations_issued > 0
    assert last.counters.leases_lapsed > 0
    assert last.counters.leases_abandoned > 0
    # some counts left out current-phase work older than the timeout
    assert any(live < in_phase for _, _, live, in_phase in checks)
    # a good share of requests reach a count: the check is not vacuous
    assert len(checks) > 0.25 * sum(f.requests for f in feeders)
    assert pool.no_work > 0


@pytest.mark.parametrize("fleet_name", sorted(FLEETS))
def test_served_trajectory_matches_recount_feeder(monkeypatch, backend,
                                                  fleet_name):
    """A served search run to the end commits the same iterates, counters,
    tables and fingerprint under the incremental feeder as under the
    recount it replaced; ``throttled`` counts exactly the cap's refusals."""
    new, new_pool, _, new_digests = _serve(
        monkeypatch, backend, fleet_name, FgdoAnmServer, digest_every=25)
    old, old_pool, _, old_digests = _serve(
        monkeypatch, backend, fleet_name, RecountFeeder, digest_every=25)
    a, b = new[-1], old[-1]
    fa, fb = a.searches[0].fgdo, b.searches[0].fgdo

    assert fa.engine.done and fb.engine.done
    assert identical_trajectories(fa.engine, fb.engine)
    assert fa.engine.best_fitness == fb.engine.best_fitness
    assert fa.engine.stats == fb.engine.stats
    assert dataclasses.asdict(a.counters) == dataclasses.asdict(b.counters)
    assert a.fingerprint() == b.fingerprint()
    assert dataclasses.asdict(new_pool) == dataclasses.asdict(old_pool)
    # the whole server state, outstanding tables included, every 25
    # messages: the prune runs once a phase yet leaves the same table
    assert new_digests == old_digests and len(new_digests) > 10
    assert fa.throttled == fb.refused_by_cap > 0
    rep = a.handle(protocol.status())
    assert rep["searches"][0]["throttled"] == fa.throttled


def _feeder(cls, *, m=4, overcommit=2.0, timeout=10.0, seed=3):
    n = N_DIMS
    return cls(x0=np.full(n, 1.0), lo=np.full(n, -10.0),
               hi=np.full(n, 10.0), step=np.full(n, 0.5),
               cfg=AnmConfig(m_regression=m, m_line_search=m,
                             max_iterations=50),
               seed=seed, val_reissue_timeout=timeout,
               overcommit=overcommit)


def _y(wu):
    return float(np.sum((np.asarray(wu.point) - 0.25) ** 2))


def test_throttled_counts_only_the_caps_refusals():
    f = _feeder(FgdoAnmServer, m=4)
    eng = f.engine
    # bootstrap: the probe's own two-copy limit is not the cap
    probes = [f.generate_work(h, 0.0) for h in range(4)]
    assert [p is not None for p in probes] == [True, True, False, False]
    assert f.throttled == 0
    eng.set_initial_fitness(_y(probes[0]))
    # regression: wanted() = 4, so the cap is 8 and two requests are refused
    wus = [f.generate_work(h, 1.0) for h in range(10)]
    assert sum(w is not None for w in wus) == 8
    assert f.throttled == 2
    # aged past the reissue timeout, that work no longer holds the cap
    assert f.generate_work(0, 12.0) is not None
    assert f.throttled == 2
    now = 12.0
    while not eng.validating:
        now += 1.0
        wu = f.generate_work(0, now)
        if wu is not None:
            f.assimilate(wu, _y(wu), 0, now)
    # validation refuses unreliable hosts and a handed-out quorum, not
    # through the cap
    before = f.throttled
    replies = [f.generate_work(h, now) for h in range(10)]
    assert any(r is None for r in replies)
    assert f.throttled == before


def test_live_count_exact_under_a_clock_that_runs_backwards():
    """Driven in lockstep with the recount feeder by a seeded script of
    requests and returns whose clock now and then steps back past the
    timeout, with state round trips on the incremental side: the same
    count at every count, and the same decisions, tables and engine at
    every step."""
    rng = np.random.default_rng(17)
    new, old = _feeder(CheckedFeeder), _feeder(RecountFeeder)
    held_new, held_old = {}, {}
    behind = 0
    t = 0.0
    for step in range(1500):
        t += float(rng.exponential(0.6))
        now = t - float(rng.uniform(0, 25.0)) if rng.random() < 0.08 else t
        if held_new and rng.random() < 0.35:
            wid = int(rng.choice(sorted(held_new)))
            wn, wo = held_new.pop(wid), held_old.pop(wid)
            host = int(rng.integers(12))
            new.assimilate(wn, _y(wn), host, now)
            old.assimilate(wo, _y(wo), host, now)
        else:
            host = int(rng.integers(12))
            wn = new.generate_work(host, now)
            wo = old.generate_work(host, now)
            assert (wn is None) == (wo is None), step
            if wn is not None:
                assert (wn.wu_id, wn.phase_id) == (wo.wu_id, wo.phase_id)
                held_new[wn.wu_id], held_old[wo.wu_id] = wn, wo
        if step % 97 == 0:
            restored = _feeder(CheckedFeeder)
            restored.registry = new.registry
            restored.load_state(new.state_dict())
            restored.throttled = new.throttled
            behind += new.behind
            new = restored
        assert list(new.outstanding) == list(old.outstanding), step
        assert new.engine.phase_id == old.engine.phase_id, step
        if new.engine.done:
            break
    assert new.engine.iteration >= 3
    # some counts ran on a clock behind the one the index had expired to
    assert behind + new.behind > 10
    assert new.throttled == old.refused_by_cap > 0
    assert identical_trajectories(new.engine, old.engine)
    assert new.engine.stats == old.engine.stats


def test_index_stays_out_of_the_checkpoint():
    """The live index is derived: ``state_dict`` is what it was without
    it, and a loaded feeder rebuilds it on its next count."""
    f = _feeder(FgdoAnmServer)
    probe = f.generate_work(0, 0.0)
    f.engine.set_initial_fitness(_y(probe))
    for h in range(5):
        f.generate_work(h, 1.0)
    d = f.state_dict()
    assert set(d) == {"engine", "last_val_issue", "outstanding"}
    g = _feeder(FgdoAnmServer)
    g.load_state(d)
    assert g._live_phase is None
    assert g._live_count(1.0) == f._live_count(1.0) == 5
    assert list(g._live) == list(f._live)
    # loading over a feeder that has moved on drops its index too
    for h in range(2):
        f.generate_work(h, 2.0)
    assert f._live_count(2.0) == 7
    f.load_state(d)
    assert f._live_count(2.0) == 5
