"""The bucket ladder's top, bounded by the device's memory (DESIGN.md §6–§7).

Where a device reports its memory limit, ``EvalBackend.warm`` derives the
widest power-of-two bucket that fits beside what the device holds: a
lane's bytes from the compiled probe bucket, checked at the top bucket
where that top cuts the ladder.  The CPU reports no limit, so these tests
patch the reading (``device_headroom``) and keep the compiler's own
memory analysis.  Pinned here: the ladder stops at the top; a submit
above it raises and compiles nothing; a ladder the top does not cut is
the ladder it was, with no extra compile; the served client pool's lazy
batches stop at the top, bit-identical to uncut batches, and count the
dispatches they cut."""
import dataclasses

import numpy as np
import pytest

from repro.core.engine import identical_trajectories
from repro.core.substrates import eval_backend
from repro.core.substrates.eval_backend import (BucketTooWide,
                                                InProcessEvalBackend,
                                                ladder_top)
from repro.core.substrates.eval_cache import CachingSubmitter
from repro.server.sim import ServerSubstrate, smoke_problem

N = 8


@pytest.fixture(scope="module")
def problem():
    return smoke_problem(n_stars=64, n_hosts=192, m=24, iterations=2)


def _headroom(monkeypatch, free):
    monkeypatch.setattr(eval_backend, "device_headroom", lambda: free)


def _ladder(backend):
    return sorted(kp for n, kp in backend._warmed if n == N)


def test_warm_stops_at_the_top_the_memory_allows(problem, monkeypatch):
    _, _, f_batch = problem
    probe = InProcessEvalBackend(f_batch)
    per_probe = probe._bucket_bytes(N, eval_backend.PROBE_LANES)
    assert per_probe > 0
    # room for four probe buckets: the widest that fits is 4 x the probe
    _headroom(monkeypatch, 4 * per_probe)
    backend = InProcessEvalBackend(f_batch).warm(N, 35_000)
    top = 4 * eval_backend.PROBE_LANES
    assert ladder_top(backend, N) == top
    assert _ladder(backend) == [1 << i for i in range(3, top.bit_length())]
    assert backend._bucket_bytes(N, top) <= 4 * per_probe
    assert backend._bucket_bytes(N, 2 * top) > 4 * per_probe

    before = (backend.compile_count, set(backend._warmed), set(backend._bufs))
    with pytest.raises(BucketTooWide, match="ladder top of 4096") as e:
        backend.submit(np.zeros((top + 1, N)))
    assert (e.value.k, e.value.kp, e.value.top) == (top + 1, 2 * top, top)
    assert (backend.compile_count, backend._warmed, set(backend._bufs)) \
        == before
    # the top itself still dispatches, on the warmed bucket
    assert len(backend(np.zeros((top, N)))) == top
    assert backend.compile_count == before[0]


def test_a_ladder_below_the_top_is_unchanged(problem, monkeypatch):
    """A cell whose ladder fits keeps its ladder, and the top's estimate
    costs no compile beyond the ladder's own buckets."""
    _, _, f_batch = problem
    _headroom(monkeypatch, 16 * 2 ** 30)
    backend = InProcessEvalBackend(f_batch).warm(N, 2048)
    ladder = [1 << i for i in range(3, 12)]
    assert _ladder(backend) == ladder
    assert backend.compile_count == len(ladder)
    assert ladder_top(backend, N) > 2048
    # a ladder no wider than the probe derives nothing
    small = InProcessEvalBackend(f_batch).warm(N, 1000)
    assert ladder_top(small, N) is None
    assert small.compile_count == len([1 << i for i in range(3, 11)])


def test_no_limit_no_top(problem):
    _, _, f_batch = problem
    assert eval_backend.device_headroom() is None      # the CPU
    backend = InProcessEvalBackend(f_batch).warm(N, 2048)
    assert ladder_top(backend, N) is None
    assert len(backend(np.zeros((3000, N)))) == 3000   # a lazy compile


def test_top_is_read_through_wrappers(problem, monkeypatch):
    _, _, f_batch = problem
    _headroom(monkeypatch, 16 * 2 ** 30)
    backend = InProcessEvalBackend(f_batch).warm(N, 2048)

    class Recorder:
        def __init__(self, inner):
            self.backend = inner

    top = ladder_top(backend, N)
    assert ladder_top(CachingSubmitter(backend), N) == top
    assert ladder_top(Recorder(CachingSubmitter(backend)), N) == top
    assert ladder_top(object(), N) is None
    assert ladder_top(backend, N + 1) is None


def test_lazy_batches_stop_at_the_top_bit_identically(problem, monkeypatch):
    """A 192-host served search with the ladder topped at 16 lanes commits
    the trajectory of the same search with uncut batches, counting each
    dispatch it cut."""
    spec, fleet, f_batch = problem
    monkeypatch.setattr(eval_backend, "PROBE_LANES", 8)
    free = InProcessEvalBackend(f_batch)._bucket_bytes(N, 8) * 2
    _headroom(monkeypatch, free)
    cut_backend = InProcessEvalBackend(f_batch)
    cut = ServerSubstrate(spec, fleet, cut_backend).run()
    assert ladder_top(cut_backend, N) == 16
    assert max(kp for _, kp in cut_backend._warmed) == 16

    _headroom(monkeypatch, None)
    whole = ServerSubstrate(spec, fleet, InProcessEvalBackend(f_batch)).run()
    assert identical_trajectories(whole.engines[0], cut.engines[0])
    assert whole.engines[0].stats == cut.engines[0].stats
    assert dataclasses.asdict(whole.server.counters) == \
        dataclasses.asdict(cut.server.counters)
    assert whole.pool.cut_batches == 0
    assert 0 < cut.pool.cut_batches < cut.pool.eval_batches
    assert cut.pool.eval_batches > whole.pool.eval_batches
    assert cut.pool.messages == whole.pool.messages
