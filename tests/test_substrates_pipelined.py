"""Pipelined bucket evaluation: async submit/collect, block speculation,
and the sync == pipelined parity contract (DESIGN.md §7).

The contracts under test:

  * WHEN a bucket is collected is invisible to the engine — at a given
    engine seed the pipelined tick loop must commit bit-identical iterates
    (and identical final engine stats) to the synchronous loop, on both
    evaluation backends, across fleet sizes, tick widths and fault rates;
  * a warmed backend performs ZERO compiles mid-run (the bucket ladder is
    compiled at construction) — pinned by the ``compile_count`` probe;
  * speculative blocks are exactly revertible: a phase flip discards the
    block and ``cancel_block`` leaves no trace on the rng stream, tickets
    or stats;
  * malicious corruption and pad masking are applied on-device from the
    mask lanes shipped with the bucket;
  * ``submit`` starts every bucket's host copy at dispatch, and a bucket
    collected at once, late or out of order returns the same bits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anm import AnmConfig
from repro.core.engine import AnmEngine, EvalResult, identical_trajectories
from repro.core.grid import GridConfig, malicious_lie
from repro.core.substrates.batched_grid import BatchedVolunteerGrid
from repro.core.substrates.eval_backend import (InProcessEvalBackend,
                                                bucket_size)
from repro.core.substrates.pod_mesh import PodMeshEvalBackend


def _quad_fitness(n=8, seed=3):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, n)).astype(np.float32)
    H = jnp.asarray(A @ A.T + n * np.eye(n, dtype=np.float32))
    x_opt = jnp.asarray(rng.uniform(-0.5, 0.5, n).astype(np.float32))

    @jax.jit
    def f_batch(xs):
        d = xs - x_opt[None, :]
        return 0.5 * jnp.einsum("mi,ij,mj->m", d, H, d)

    return f_batch, n


def _run_grid(f_batch, n, *, pipelined, n_hosts=256, tick_batch=None,
              failure_prob=0.1, malicious_prob=0.02, m=48, iters=4,
              backend=None, grid_seed=3, engine_seed=7):
    cfg = AnmConfig(m_regression=m, m_line_search=m, max_iterations=iters)
    gcfg = GridConfig(n_hosts=n_hosts, failure_prob=failure_prob,
                      malicious_prob=malicious_prob, seed=grid_seed)
    engine = AnmEngine(np.ones(n), -10 * np.ones(n), 10 * np.ones(n),
                       0.5 * np.ones(n), cfg, seed=engine_seed)
    grid = BatchedVolunteerGrid(f_batch, gcfg, tick_batch=tick_batch,
                                backend=backend, pipelined=pipelined)
    stats = grid.run(engine)
    return engine, stats


# -- pipelined == sync parity --------------------------------------------------

@pytest.mark.parametrize("n_hosts,tick_batch,failure_prob,malicious_prob", [
    (256, None, 0.0, 0.0),
    (256, 4, 0.1, 0.02),
    (128, 8, 0.3, 0.1),
    (512, 16, 0.05, 0.01),
])
def test_pipelined_matches_sync_seeded_sweep(n_hosts, tick_batch,
                                             failure_prob, malicious_prob):
    """Bit-identical committed iterates, sim time and final engine stats,
    whether buckets are collected synchronously or ride the pipeline."""
    f_batch, n = _quad_fitness()
    kw = dict(n_hosts=n_hosts, tick_batch=tick_batch,
              failure_prob=failure_prob, malicious_prob=malicious_prob)
    e_pipe, s_pipe = _run_grid(f_batch, n, pipelined=True, **kw)
    e_sync, s_sync = _run_grid(f_batch, n, pipelined=False, **kw)
    assert identical_trajectories(e_pipe, e_sync)
    assert e_pipe.stats == e_sync.stats
    assert s_pipe.sim_time == s_sync.sim_time
    assert s_pipe.completed == s_sync.completed
    assert s_pipe.ticks == s_sync.ticks
    assert s_pipe.corrupted == s_sync.corrupted


def test_pipelined_matches_sync_on_pod_backend():
    f_batch, n = _quad_fitness()
    e_pipe, _ = _run_grid(f_batch, n, pipelined=True, tick_batch=4,
                          backend=PodMeshEvalBackend(f_batch))
    e_sync, _ = _run_grid(f_batch, n, pipelined=False, tick_batch=4)
    assert identical_trajectories(e_pipe, e_sync)


def test_pipeline_actually_runs_deep_and_speculates():
    """A fleet tight relative to the overcommit cap splits issuance across
    ticks, so mid-phase top-ups must ride the speculative peek path while
    earlier buckets are still in flight — and parity must still hold."""
    f_batch, n = _quad_fitness()
    kw = dict(n_hosts=128, tick_batch=8, m=128, iters=3,
              failure_prob=0.15, malicious_prob=0.02)
    e_pipe, s_pipe = _run_grid(f_batch, n, pipelined=True, **kw)
    e_sync, _ = _run_grid(f_batch, n, pipelined=False, **kw)
    assert s_pipe.max_in_flight > 1        # the pipeline really ran ahead
    assert s_pipe.spec_blocks > 0          # speculative issuance engaged
    assert s_pipe.spec_discarded == 0      # exact no-flip prediction
    assert identical_trajectories(e_pipe, e_sync)


# -- zero compiles after construction -----------------------------------------

@pytest.mark.parametrize("backend_cls", [InProcessEvalBackend,
                                         PodMeshEvalBackend])
def test_warmed_backend_never_compiles_mid_run(backend_cls):
    """Constructing with n_dims/max_bucket compiles the whole bucket
    ladder up front; a full grid run (both loop modes) must not add a
    single trace."""
    f_batch, n = _quad_fitness()
    be = backend_cls(f_batch, n_dims=n, max_bucket=128)
    warmed = be.compile_count
    assert warmed > 0
    _run_grid(f_batch, n, pipelined=True, m=48, backend=be)
    _run_grid(f_batch, n, pipelined=False, m=48, backend=be)
    assert be.compile_count == warmed


# -- block speculation: peek / cancel -----------------------------------------

def _engine_pair(n=4, m=20):
    cfg = AnmConfig(m_regression=m, m_line_search=m, max_iterations=3)
    mk = lambda: AnmEngine(np.ones(n), -10 * np.ones(n), 10 * np.ones(n),
                           0.5 * np.ones(n), cfg, seed=5)
    return mk(), mk()


def _skip_bootstrap(engine, f):
    reqs = engine.generate()
    engine.assimilate([EvalResult(r, f(r.point)) for r in reqs])
    while engine.validating:
        reqs = engine.generate()
        if not reqs:
            break
        engine.assimilate([EvalResult(r, f(r.point)) for r in reqs])


def test_peek_then_cancel_is_invisible():
    """cancel_block rewinds the rng stream, ticket counter and issuance
    stat: a twin engine that never speculated generates the identical
    block afterwards."""
    f = lambda p: float(np.sum(np.asarray(p) ** 2))
    a, b = _engine_pair()
    _skip_bootstrap(a, f)
    _skip_bootstrap(b, f)
    peeked = a.peek_block(7)
    assert peeked is not None
    a.cancel_block()
    assert a.stats.issued == b.stats.issued
    blk_a, blk_b = a.generate_block(7), b.generate_block(7)
    np.testing.assert_array_equal(blk_a[0], blk_b[0])      # tickets
    np.testing.assert_array_equal(blk_a[2], blk_b[2])      # points
    np.testing.assert_array_equal(blk_a[3], blk_b[3])      # alphas
    assert a.stats.issued == b.stats.issued


def test_phase_flip_discards_speculative_block():
    """The pipelined grid's bet: a block peeked for phase P is discarded
    when assimilation flips the phase.  After cancel_block the engine must
    continue exactly like a twin that never speculated — same line-search
    blocks, same stats."""
    f = lambda p: float(np.sum(np.asarray(p) ** 2))
    spec, plain = _engine_pair(m=20)
    issued = {}
    for e in (spec, plain):
        _skip_bootstrap(e, f)
        assert e.phase == "regression"
        # the whole regression phase is issued up front (identical draws)
        issued[e] = e.generate_block(20)
    for e, (tk, ph, pts, al) in issued.items():
        # 19 of 20 results land: one short of the flip
        e.assimilate_arrays(ph + np.zeros(19, np.int64), tk[:19], pts[:19],
                            al[:19], np.full(19, -1),
                            np.sum(pts[:19] ** 2, axis=1))
    # the speculating engine peeks the next block, betting on no flip...
    peeked = spec.peek_block(6)
    assert peeked is not None and peeked[1] == spec.phase_id
    # ...but the m-th result lands and the phase flips to the line search
    for e, (tk, ph, pts, al) in issued.items():
        e.assimilate_arrays(np.array([ph]), tk[19:], pts[19:], al[19:],
                            np.array([-1]),
                            np.sum(pts[19:] ** 2, axis=1))
        assert e.phase == "linesearch"
    # the peeked block is stale under the new phase id: discard it
    assert peeked[1] != spec.phase_id
    spec.cancel_block()
    # from here, both engines must be indistinguishable
    assert spec.phase == plain.phase
    assert spec.stats == plain.stats
    ba, bb = spec.generate_block(10), plain.generate_block(10)
    np.testing.assert_array_equal(ba[0], bb[0])      # tickets
    np.testing.assert_array_equal(ba[2], bb[2])      # points
    np.testing.assert_array_equal(ba[3], bb[3])      # alphas


def test_peek_cancel_while_validation_pending_rewinds_ticket_state():
    """A peek taken while quorum replicas are pending generates nothing
    (blocks only exist in regression/line-search), but the cancel must
    rewind the validation ticket state too — the snapshot carries
    ``validations_issued`` and the pending-replica budget, so a substrate
    interleaving many engines can peek anywhere without corrupting a
    pending quorum."""
    f = lambda p: float(np.sum(np.asarray(p) ** 2))
    spec, plain = _engine_pair()
    first = {}
    for e in (spec, plain):
        reqs = e.generate()                    # the f(x0) bootstrap probe
        e.assimilate([EvalResult(r, f(r.point)) for r in reqs])
        assert e.validating and e.validation_pending == e.quorum
        # hand out ONE replica: validation tickets are now mid-stream
        [r1] = e.generate(1)
        assert r1.validates is not None and e.validation_pending == e.quorum - 1
        first[e] = r1
    # the speculating engine peeks mid-validation...
    assert spec.peek_block(5) is None
    spec.cancel_block()
    # ...and must be indistinguishable from the twin that never did
    assert spec.validation_pending == plain.validation_pending
    assert spec.stats == plain.stats
    assert spec._next_ticket == plain._next_ticket
    # the remaining replica and the rest of the validation line up exactly
    [ra], [rb] = spec.generate(), plain.generate()
    assert ra.ticket == rb.ticket and ra.validates == rb.validates
    for e, r in ((spec, ra), (plain, rb)):
        e.assimilate([EvalResult(q, f(q.point)) for q in (first[e], r)])
    assert spec.phase == plain.phase == "regression"
    assert spec.stats == plain.stats


def test_peek_cancel_during_linesearch_validation_keeps_quorum_exact():
    """Same contract deeper in the run: drive a full regression + line
    search to the candidate-validation phase, peek/cancel there, and
    check the twin still validates and commits identically."""
    f = lambda p: float(np.sum(np.asarray(p) ** 2))
    spec, plain = _engine_pair(m=12)
    for e in (spec, plain):
        _skip_bootstrap(e, f)
        while not e.validating:                # regression + line search
            reqs = e.generate()
            e.assimilate([EvalResult(r, f(r.point)) for r in reqs])
        assert e.validation_pending == e.quorum
    assert spec.peek_block() is None
    spec.cancel_block()
    assert spec.validation_pending == plain.validation_pending
    assert spec.stats == plain.stats
    for e in (spec, plain):                    # finish the validation
        reqs = e.generate()
        e.assimilate([EvalResult(r, f(r.point)) for r in reqs])
    assert spec.phase == plain.phase
    assert spec.iteration == plain.iteration
    assert spec.best_fitness == plain.best_fitness
    assert spec.stats == plain.stats


# -- on-device corruption and masking -----------------------------------------

def test_submit_applies_corruption_lanes_on_device():
    f_batch, n = _quad_fitness()
    be = InProcessEvalBackend(f_batch)
    pts = np.random.default_rng(0).uniform(-1, 1, (13, n))
    honest = be(pts)
    u = np.full(13, np.nan)
    u[[2, 5, 11]] = [0.2, 0.5, 0.8]
    ys = be(pts, u)
    lied = ~np.isnan(u)
    np.testing.assert_array_equal(ys[~lied], honest[~lied])
    # the lie is computed in the device's f32 lanes — compare against the
    # same formula evaluated at f32 precision
    expect = np.asarray(malicious_lie(honest[lied].astype(np.float32),
                                      u[lied].astype(np.float32)), np.float64)
    np.testing.assert_allclose(ys[lied], expect, rtol=1e-6)
    assert (ys[lied] < honest[lied]).all()   # always an under-report


def test_async_submit_collect_matches_sync_call():
    f_batch, n = _quad_fitness()
    be = InProcessEvalBackend(f_batch)
    rng = np.random.default_rng(1)
    blocks = [rng.uniform(-1, 1, (k, n)) for k in (3, 17, 64)]
    handles = [be.submit(p) for p in blocks]       # all in flight at once
    for p, h in zip(blocks, handles):
        np.testing.assert_array_equal(be.collect(h), be(p))


@pytest.mark.parametrize("backend_cls", [InProcessEvalBackend,
                                         PodMeshEvalBackend])
def test_submit_starts_the_host_copy_of_every_bucket(backend_cls,
                                                     monkeypatch):
    """The read-back is requested at dispatch, once a bucket and for the
    bucket's own result; warming the ladder requests none."""
    from jax._src.array import ArrayImpl
    copied = []
    start = ArrayImpl.copy_to_host_async

    def counting(self):
        copied.append(self)
        return start(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", counting)
    f_batch, n = _quad_fitness()
    be = backend_cls(f_batch, n_dims=n, max_bucket=64)
    assert copied == []
    rng = np.random.default_rng(4)
    handles = [be.submit(rng.uniform(-1, 1, (k, n))) for k in (1, 9, 64)]
    assert len(copied) == 3
    assert all(c is h.ys for c, h in zip(copied, handles))
    for h in handles:
        be.collect(h)
    be(rng.uniform(-1, 1, (5, n)))
    assert len(copied) == 4


#: a small ladder: bucket floor, remainders, a full bucket, two doublings
LADDER = (1, 5, 8, 13, 32, 50)


@pytest.mark.parametrize("k", LADDER)
def test_collect_order_and_lateness_keep_values_bit_identical(k):
    """A handle collected at once, one collected after later buckets, and
    handles collected in reverse order all return what the synchronous
    call returns, bit for bit: honest lanes, malicious lanes, and the pad
    lanes the device masks to NaN."""
    f_batch, n = _quad_fitness()
    be = InProcessEvalBackend(f_batch)
    rng = np.random.default_rng(10 + k)
    blocks = []
    for _ in range(3):
        u = np.where(rng.random(k) < 0.4, rng.uniform(0.2, 0.8, k), np.nan)
        blocks.append((rng.uniform(-1, 1, (k, n)), u))
    sync = [be(p, u) for p, u in blocks]
    assert any((~np.isnan(u)).any() for _, u in blocks)

    assert_equal = np.testing.assert_array_equal
    assert_equal(be.collect(be.submit(*blocks[0])), sync[0])   # at once
    late = be.submit(*blocks[0])
    for (p, u), ref in zip(blocks[1:], sync[1:]):               # in between
        assert_equal(be.collect(be.submit(p, u)), ref)
    assert_equal(be.collect(late), sync[0])                     # late
    handles = [be.submit(p, u) for p, u in blocks]
    for h, ref in reversed(list(zip(handles, sync))):          # reversed
        assert_equal(be.collect(h), ref)
        pad = np.asarray(h.ys)[h.k:]
        assert pad.size == h.kp - k and np.isnan(pad).all()


def test_staging_ring_survives_deep_inflight_reuse():
    """Many in-flight submissions of the SAME bucket shape must not
    corrupt each other (CPU zero-copy aliasing is real: the ring exists
    for exactly this)."""
    f_batch, n = _quad_fitness()
    be = InProcessEvalBackend(f_batch)
    rng = np.random.default_rng(2)
    blocks = [rng.uniform(-1, 1, (16, n)) for _ in range(6)]
    expected = [np.asarray(f_batch(jnp.asarray(p, jnp.float32)), np.float64)
                for p in blocks]
    handles = [be.submit(p) for p in blocks]
    for h, ref in zip(handles, expected):
        np.testing.assert_array_equal(be.collect(h), ref)


def test_staging_ring_overrun_raises_instead_of_corrupting():
    """Restaging a slot whose bucket is uncollected would silently alias
    a buffer the device may still read — submit must refuse loudly, slot
    by slot, so out-of-order collects cannot defeat the guard."""
    from repro.core.substrates.eval_backend import STAGING_RING
    f_batch, n = _quad_fitness()
    be = InProcessEvalBackend(f_batch)
    pts = np.random.default_rng(0).uniform(-1, 1, (16, n))
    handles = [be.submit(pts) for _ in range(STAGING_RING)]
    with pytest.raises(RuntimeError, match="uncollected"):
        be.submit(pts)
    # freeing an arbitrary LATER slot must not unblock the ring: the next
    # submit would restage slot 0, whose bucket is still in flight
    be.collect(handles[5])
    with pytest.raises(RuntimeError, match="uncollected"):
        be.submit(pts)
    be.collect(handles[0])                      # the aliased slot itself
    be.collect(be.submit(pts))
    for i, h in enumerate(handles):
        if i not in (0, 5):
            be.collect(h)


def test_grid_span_self_times_add_up_across_repeated_runs(monkeypatch):
    """The grid's host time is read from program spans (``obs/spans.py``):
    across repeated run() calls on one grid every self time stays
    non-negative, the totals only grow, and the self times of all spans,
    with the bookkeeping of the nested ones, add up to the grid's own
    enclosing ``fleet.step`` time."""
    from repro.obs import spans

    monkeypatch.setattr(spans, "enabled", lambda: True)
    spans.reset()
    f_batch, n = _quad_fitness()
    cfg = AnmConfig(m_regression=24, m_line_search=24, max_iterations=2)
    gcfg = GridConfig(n_hosts=64, failure_prob=0.05, malicious_prob=0.0,
                      seed=3)
    grid = BatchedVolunteerGrid(f_batch, gcfg)
    before = {}
    try:
        for seed in (1, 2):
            engine = AnmEngine(np.ones(n), -10 * np.ones(n), 10 * np.ones(n),
                               0.5 * np.ones(n), cfg, seed=seed)
            grid.run(engine)
            tot = spans.totals()
            assert {"fleet.step", "engine.generate", "engine.assimilate",
                    "engine.finish", "backend.submit", "backend.collect",
                    "backend.wait"} <= set(tot)
            for name, t in tot.items():
                assert 0 <= t["self_ns"] <= t["total_ns"], name
                b = before.get(name, {"count": 0, "total_ns": 0})
                assert t["count"] > b["count"], name
                assert t["total_ns"] >= b["total_ns"], name
            # the grid's own fleet.step time is the self times of all its
            # spans plus the bookkeeping of the spans nested in it
            assert sum(t["self_ns"] + t["overhead_ns"]
                       for t in tot.values()) \
                - tot["fleet.step"]["overhead_ns"] == \
                tot["fleet.step"]["total_ns"]
            before = tot
    finally:
        spans.reset()
