"""Pod-mesh evaluation backend: bucket framing and shard_map parity
(DESIGN.md §6).  The production 16x16 mesh runs in a child process that
sees 512 forced host devices, here for the batched grid and the
coalesced orchestrator, and in ``tests/test_sigkill_restore.py`` for the
work server.

The contract under test: WHERE a workunit block is evaluated is invisible
to the engine — the pod-mesh backend must commit bit-identical iterates to
the in-process backend at the same engine seed and grid config.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.anm import AnmConfig
from repro.core.engine import AnmEngine
from repro.core.grid import GridConfig
from repro.core.substrates.batched_grid import BatchedVolunteerGrid
from repro.core.substrates.eval_backend import (InProcessEvalBackend,
                                                bucket_size)
from repro.core.substrates.pod_mesh import PodMeshEvalBackend, make_data_mesh


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


# -- bucket framing -----------------------------------------------------------

def test_bucket_size_power_of_two_with_floor():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(500) == 512
    assert bucket_size(3, min_bucket=16) == 16
    with pytest.raises(ValueError):
        bucket_size(4, min_bucket=12)        # not a power of two


def test_backend_pads_to_buckets_and_masks_remainder():
    f_batch, n = _quad_fitness()
    be = InProcessEvalBackend(f_batch)
    kps = []
    for k in (1, 5, 8, 13, 64, 100):
        pts = np.random.default_rng(k).uniform(-1, 1, (k, n))
        handle = be.submit(pts)
        kps.append(handle.kp)
        ys = be.collect(handle)
        assert ys.shape == (k,)              # remainder masked, not dropped
        ref = np.asarray(f_batch(jnp.asarray(pts, jnp.float32)), np.float64)
        np.testing.assert_array_equal(ys, ref)
    assert kps == [bucket_size(k) for k in (1, 5, 8, 13, 64, 100)]


def test_min_bucket_validated_directly():
    """The floor argument is validated as a power of two — not silently
    rounded through bucket_size — and lives in one documented place."""
    from repro.core.substrates.eval_backend import DEFAULT_MIN_BUCKET
    f_batch, _ = _quad_fitness()
    assert InProcessEvalBackend(f_batch).min_bucket == DEFAULT_MIN_BUCKET
    assert InProcessEvalBackend(f_batch, min_bucket=2).min_bucket == 2
    for bad in (0, 3, 12, -8):
        with pytest.raises(ValueError):
            InProcessEvalBackend(f_batch, min_bucket=bad)


def test_pod_backend_bucket_floor_is_shard_count():
    f_batch, _ = _quad_fitness()
    pod = PodMeshEvalBackend(f_batch)
    assert pod.min_bucket >= pod.n_shards
    assert pod.min_bucket & (pod.min_bucket - 1) == 0


# -- backend value parity ------------------------------------------------------

def test_pod_backend_values_match_in_process_exactly():
    f_batch, n = _quad_fitness()
    inp = InProcessEvalBackend(f_batch)
    pod = PodMeshEvalBackend(f_batch, mesh=make_data_mesh())
    for k in (1, 7, 32, 200):
        pts = np.random.default_rng(k).uniform(-2, 2, (k, n))
        np.testing.assert_array_equal(inp(pts), pod(pts))


# -- end-to-end committed-iterate parity ---------------------------------------

def test_pod_and_in_process_backends_commit_identical_iterates():
    """Same engine seed + same grid config => bit-identical committed
    centers, fitness history, iteration counts and sim time, whichever
    backend evaluates the buckets."""
    f_batch, n = _quad_fitness()
    cfg = AnmConfig(m_regression=48, m_line_search=48, max_iterations=4)
    grid_cfg = GridConfig(n_hosts=256, failure_prob=0.1,
                          malicious_prob=0.02, seed=3)

    def run(backend):
        engine = AnmEngine(np.ones(n), -10 * np.ones(n), 10 * np.ones(n),
                           0.5 * np.ones(n), cfg, seed=7)
        stats = BatchedVolunteerGrid(f_batch, grid_cfg,
                                     backend=backend).run(engine)
        return engine, stats

    e_in, s_in = run(None)                    # default in-process
    e_pod, s_pod = run(PodMeshEvalBackend(f_batch))
    assert e_in.iteration == e_pod.iteration
    assert len(e_in.history) == len(e_pod.history)
    for a, b in zip(e_in.history, e_pod.history):
        np.testing.assert_array_equal(a.center, b.center)
        assert a.best_fitness == b.best_fitness
    assert s_in.sim_time == s_pod.sim_time
    assert s_in.completed == s_pod.completed


# -- the real partitioning, on 512 forced host devices -------------------------

# The batched grid sync and pipelined through both backends, then a
# coalesced two-search portfolio through both, at the old dryrun smoke's
# size; prints what the test compares.
_PRODUCTION_MESH_CHILD = """
import json
import numpy as np
from repro.core.anm import AnmConfig
from repro.core.engine import AnmEngine, identical_trajectories
from repro.core.grid import GridConfig
from repro.core.orchestrator import (FleetScheduler, SearchDirector,
                                     multi_start_specs)
from repro.core.substrates.batched_grid import BatchedVolunteerGrid
from repro.core.substrates.eval_backend import InProcessEvalBackend
from repro.core.substrates.pod_mesh import PodMeshEvalBackend
from repro.data import sdss

stripe = sdss.make_stripe("podmesh", n_stars=500, seed=17)
f_batch, _ = sdss.make_fitness(stripe)
x0 = np.clip(stripe.truth + np.random.default_rng(3).normal(0, 0.2, 8)
             .astype(np.float32), sdss.LO, sdss.HI)
anm = AnmConfig(m_regression=32, m_line_search=32, max_iterations=2)
fleet = GridConfig(n_hosts=512, failure_prob=0.05, malicious_prob=0.01,
                   seed=9)
in_process, pod = InProcessEvalBackend(f_batch), PodMeshEvalBackend(f_batch)

def grid(backend, pipelined):
    engine = AnmEngine(x0, sdss.LO, sdss.HI, sdss.DEFAULT_STEP, anm, seed=7)
    BatchedVolunteerGrid(f_batch, fleet, backend=backend,
                         pipelined=pipelined).run(engine)
    return engine

def portfolio(backend):
    sched = FleetScheduler(backend, fleet)
    specs = multi_start_specs(sched, x0, sdss.LO, sdss.HI, sdss.DEFAULT_STEP,
                              anm, 2, seed=7, jitter=0.3)
    return SearchDirector(sched, specs).run().outcomes

def same(a, b):
    return identical_trajectories(a, b) and a.stats == b.stats

ref = grid(in_process, False)
runs = [grid(in_process, True), grid(pod, False), grid(pod, True)]
coalesced, in_process_coalesced = portfolio(pod), portfolio(in_process)
print(json.dumps({
    "n_shards": pod.n_shards, "iterations": ref.iteration,
    "grid": [same(ref, e) for e in runs],
    "solo": [same(o.engine, o.spec.solo_run(pod)) for o in coalesced],
    "across_backends": [same(a.engine, b.engine) for a, b in
                        zip(coalesced, in_process_coalesced)]}))
"""


def test_production_mesh_grid_and_portfolio_match_in_process(child_env):
    """On the production (data=16, model=16) mesh the batched grid, sync
    and pipelined, commits the in-process sync trajectory, and each
    search of a coalesced portfolio commits its solo run and its
    in-process twin."""
    env = dict(child_env,
               XLA_FLAGS="--xla_force_host_platform_device_count=512")
    r = subprocess.run([sys.executable, "-c", _PRODUCTION_MESH_CHILD],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["n_shards"] == 16
    assert got["iterations"] == 2
    assert got["grid"] == [True] * 3
    assert got["solo"] == [True] * 2
    assert got["across_backends"] == [True] * 2
