"""LM-loss evaluation backend: the model stack as the engine's fitness.

Pins the DESIGN.md §11 contracts on the single real CPU device: shared
subspace machinery, zero compiles after warm, bucket-width invariance,
sync == pipelined trajectories, and unmodified composition with
``CachingSubmitter`` and the work server.  The pod variant, with θ0 and
the basis stored sharded over ``model``, runs in a child process on the
production 16x16 mesh of 512 forced host devices (and on a (2, 2) mesh
of chips in ``chip_smoke.py --chips 4``).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.subspace import (SubspaceProjection, orthonormal_basis,
                                 ravel_pytree, tree_lift)
from repro.core.substrates.lm_loss import (LmLossEvalBackend, LmWorkload,
                                           make_lm_workload)

K = 4


@pytest.fixture(scope="module")
def workload() -> LmWorkload:
    # tiny on purpose: 1×16 tokens through the 2-layer rwkv6 smoke config
    # keeps each lane's forward in the milliseconds
    return make_lm_workload("rwkv6-7b", k=K, batch_size=1, seq_len=16,
                            seed=1)


@pytest.fixture(scope="module")
def backend(workload) -> LmLossEvalBackend:
    return LmLossEvalBackend(workload, n_dims=K, max_bucket=16)


def _eval(backend, pts, mal_u=None, tags=None):
    pts = np.atleast_2d(pts)
    if mal_u is None:
        mal_u = np.full(len(pts), np.nan)
    if tags is None:
        tags = list(range(len(pts)))
    return backend.collect(backend.submit(pts, mal_u, tags))


# ---------------------------------------------------------------------------
# the shared subspace chart
# ---------------------------------------------------------------------------

class TestSubspaceProjection:
    def test_basis_orthonormal(self, workload):
        basis = workload.proj.basis
        gram = basis @ basis.T
        np.testing.assert_allclose(np.asarray(gram), np.eye(K), atol=1e-5)

    def test_lift_zero_is_theta0(self, workload):
        proj = workload.proj
        lifted = proj.lift(np.zeros(K, np.float32))
        np.testing.assert_array_equal(np.asarray(ravel_pytree(lifted)[0]),
                                      np.asarray(proj.flat0))

    def test_tree_lift_matches_flat_lift(self, workload):
        # the leaf-wise lift (what the backend shards) and the flat lift
        # (what the optimizer steps along) are the same map
        # (both lifts round back to the leaf dtypes — bf16 for the smoke
        # configs — so compare after the same unravel round-trip)
        proj = workload.proj
        c = np.asarray(np.linspace(-0.3, 0.4, K), np.float32)
        flat_of_tree, _ = ravel_pytree(proj.lift(c))
        flat_of_flat, _ = ravel_pytree(proj.unravel(proj.lift_flat(c)))
        np.testing.assert_allclose(np.asarray(flat_of_tree),
                                   np.asarray(flat_of_flat),
                                   rtol=1e-5, atol=1e-6)

    def test_anchor_first_row(self):
        rng = np.random.default_rng(5)
        anchor = rng.normal(size=32).astype(np.float32)
        import jax
        basis = orthonormal_basis(jax.random.key(0), 32, 3, anchor=anchor)
        unit = anchor / np.linalg.norm(anchor)
        cos = np.abs(np.asarray(basis)[0] @ unit)
        assert cos > 1 - 1e-5

    def test_optimizer_consumes_same_machinery(self, workload):
        # the in-process subspace-Newton step builds its chart from the
        # SAME module — one lift, two consumers
        import jax

        from repro.core import subspace_newton as sn
        key = jax.random.key(2)
        flat = workload.proj.flat0
        mom = np.asarray(np.ones_like(flat))
        np.testing.assert_array_equal(
            np.asarray(sn.make_basis(key, flat, mom, 3)),
            np.asarray(orthonormal_basis(key, flat.shape[0], 3,
                                         anchor=mom)))


# ---------------------------------------------------------------------------
# the backend contract
# ---------------------------------------------------------------------------

class TestLmLossBackend:
    def test_zero_compiles_after_warm(self, workload):
        be = LmLossEvalBackend(workload)
        be.warm(K, 16)
        c0 = be.compile_count
        assert c0 > 0
        rng = np.random.default_rng(0)
        for n in (1, 3, 7, 12):
            _eval(be, rng.uniform(-0.5, 0.5, (n, K)))
        assert be.compile_count == c0

    def test_loss_matches_direct_forward(self, workload, backend):
        # lane value == an independent jit of loss(lift(c)) — the backend
        # adds framing, not arithmetic
        import jax
        import jax.numpy as jnp
        from repro.models import transformer as T

        loss_fn = T.make_loss_fn(workload.cfg)
        batch = {k: jnp.asarray(v) for k, v in workload.batch.items()}
        c = np.asarray([0.2, -0.1, 0.4, -0.3])
        direct = jax.jit(lambda cc: loss_fn(
            tree_lift(workload.proj.theta0, workload.proj.basis_tree, cc),
            batch)[0])(jnp.asarray(c, jnp.float32))
        ys = _eval(backend, c)
        np.testing.assert_allclose(ys[0], float(direct), rtol=0, atol=0)

    def test_bucket_width_invariance(self, backend):
        # the same point rides buckets of width 8 and 16 bitwise-unchanged
        # (per-lane lax.map: width cannot touch a lane's program)
        rng = np.random.default_rng(1)
        pts = rng.uniform(-0.5, 0.5, (12, K))
        wide = _eval(backend, pts)
        narrow = np.concatenate([_eval(backend, pts[:4]),
                                 _eval(backend, pts[4:8]),
                                 _eval(backend, pts[8:])])
        np.testing.assert_array_equal(wide, narrow)

    def test_malicious_lanes_corrupted(self, backend):
        pts = np.tile(np.asarray([0.1, 0.2, -0.2, 0.3]), (2, 1))
        honest = _eval(backend, pts)
        lied = _eval(backend, pts, mal_u=np.asarray([np.nan, 0.5]))
        assert honest[0] == lied[0]          # honest lane untouched
        assert lied[1] != honest[1]          # corrupted on-device
        assert np.isfinite(lied[1])

    def test_engine_box_shape(self, workload):
        assert workload.x0.shape == (K,)
        assert np.all(workload.lo < workload.hi)
        assert np.all(workload.step > 0)

    def test_caching_submitter_unmodified(self, workload, backend):
        # the §10 memo layer in front of THIS backend: bit-equal values,
        # warm resubmission fully served
        from repro.core.substrates.eval_cache import (CachingSubmitter,
                                                      EvalCache)
        cache = EvalCache(fingerprint="test_lm_loss")
        sub = CachingSubmitter(backend, cache)
        rng = np.random.default_rng(2)
        pts = rng.uniform(-0.5, 0.5, (6, K))
        mal = np.full(6, np.nan)
        cold = sub.collect(sub.submit(pts, mal))
        np.testing.assert_array_equal(cold, _eval(backend, pts))
        misses = cache.stats.misses
        warm = sub.collect(sub.submit(pts, mal))
        np.testing.assert_array_equal(cold, warm)
        assert cache.stats.misses == misses
        assert cache.stats.hits >= len(pts)


class TestGridTrajectories:
    @pytest.fixture(scope="class")
    def problem(self):
        from repro.server.sim import lm_problem
        # the canonical problem builder the example uses,
        # scaled down: 16 hosts, one iteration
        spec, fleet, wl = lm_problem(arch="rwkv6-7b", k=K, n_hosts=16,
                                     m=6, iterations=1)
        return spec, fleet, wl

    def test_pipelined_equals_sync(self, problem):
        from repro.core.engine import identical_trajectories
        from repro.core.substrates.batched_grid import BatchedVolunteerGrid
        spec, fleet, wl = problem
        be = LmLossEvalBackend(wl, n_dims=wl.k, max_bucket=32)

        def run(pipelined):
            engine = spec.build_engine()
            BatchedVolunteerGrid(None, fleet, backend=be,
                                 pipelined=pipelined).run(engine)
            return engine

        e_sync, e_pipe = run(False), run(True)
        assert identical_trajectories(e_sync, e_pipe)
        assert np.isfinite(e_sync.best_fitness)

    @pytest.mark.server
    def test_work_server_unmodified(self, problem):
        # the full wire-protocol stack over the LM objective, crash and
        # restore included (SimulatedCrash, no subprocess)
        import tempfile

        from repro.server.sim import (ServerSubstrate, SimulatedCrash,
                                      result_doc)
        spec, fleet, wl = problem
        be = LmLossEvalBackend(wl)
        base = result_doc(ServerSubstrate(spec, fleet, be).run())
        assert base["iteration"] >= 1
        kill_after = max(40, int(0.4 * base["pool"]["messages"]))
        with tempfile.TemporaryDirectory() as ckpt:
            with pytest.raises(SimulatedCrash):
                ServerSubstrate(spec, fleet, be, ckpt_dir=ckpt,
                                snapshot_every=20,
                                max_messages=kill_after).run()
            res = result_doc(ServerSubstrate(spec, fleet, be,
                                             ckpt_dir=ckpt).run(resume=True))
        assert res["history"] == base["history"]
        assert res["engine_stats"] == base["engine_stats"]


@pytest.mark.server
def test_production_mesh_child_commits_the_in_process_trajectory(
        child_env, tmp_path):
    """``python -m repro.server.sim --problem lm`` served through the pod
    backend on the production (data=16, model=16) mesh commits exactly
    what an in-process child commits, at the old dryrun smoke's size.
    The two children run side by side, each under a deadline."""
    size = ["--problem", "lm", "--n-hosts", "48", "--m", "12",
            "--iterations", "2", "--k", "6"]
    forced = dict(child_env,
                  XLA_FLAGS="--xla_force_host_platform_device_count=512")
    runs = {"in_process": (child_env, []),
            "pod_mesh": (forced, ["--backend", "pod_mesh"])}
    procs = {name: subprocess.Popen(
                 [sys.executable, "-m", "repro.server.sim", *size, *extra,
                  "--out", str(tmp_path / f"{name}.json")],
                 env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                 text=True)
             for name, (env, extra) in runs.items()}
    deadline = time.monotonic() + 120
    try:
        for proc in procs.values():
            out, _ = proc.communicate(
                timeout=max(1.0, deadline - time.monotonic()))
            assert proc.returncode == 0, out
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    docs = {}
    for name in runs:
        with open(os.path.join(tmp_path, f"{name}.json")) as f:
            docs[name] = json.load(f)
    base, pod = docs["in_process"], docs["pod_mesh"]
    assert (base["data_shards"], pod["data_shards"]) == (1, 16)
    assert base["iteration"] == 2
    for key in ("history", "iteration", "best_fitness", "engine_stats"):
        assert pod[key] == base[key], key
