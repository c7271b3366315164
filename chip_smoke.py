#!/usr/bin/env python3
"""Chip smoke: the paper deployment end to end on a TPU.

    python chip_smoke.py             # one chip: phases 1-6
    python chip_smoke.py --chips 4   # four chips: the cross-chip paths only

The deployment is the paper's (§VI): ``sdss.stripe79()`` (100k stars,
4096 quadrature points) fitted with ``configs/paper_anm.CONFIG`` (8
parameters, m = 1000 per phase, validation quorum 2, 2048 hosts of which
5% fail and 1% lie).  One chip, one process, in order:

1. device check: platform, device kind and count; no TPU means exit 2;
2. a 1024-point bucket of the paper-size fitness against an independent
   float64 NumPy likelihood (max relative error <= ``FITNESS_RTOL``);
3. the engine's phase-finish fit (``_regression_direction``) at m = 1000
   against a float64 NumPy least-squares fit and Newton step (direction
   cosine >= ``DIRECTION_COS``), with the gram kernel in the program;
4. a pipelined ``BatchedVolunteerGrid`` search over
   ``InProcessEvalBackend``: 3 committed iterations, zero compiles after
   ``warm()``, finite fitness, at least one improvement;
5. the same search served by ``WorkServer`` through ``ServerSubstrate``
   and the loopback ``SimClientPool`` (real request_work/report_result
   messages), with the same checks;
6. the LM objective (rwkv6-7b at smoke width) through
   ``LmLossEvalBackend`` with the wkv6 kernel in the program, against the
   same backend built with ``use_kernels=False``.

``--chips 4`` runs only what exists across chips: ``PodMeshEvalBackend``
sharded over all chips against ``InProcessEvalBackend`` on the first, on
the same seeded search, and ``LmLossEvalBackend`` on a (data=2, model=2)
mesh against ``mesh=None``.

Each phase is a function that a CPU test calls at a tiny size; only
``main`` insists on a TPU.  A failed check raises and the script exits
non-zero.  Progress and timings go to stdout as they happen; the last
line is the JSON verdict, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

#: fitness agreement with the float64 reference: f32 rounding over a mean
#: of 1e5 log-densities stays near 1e-6, so 1e-4 leaves room for the TPU's
#: transcendentals and nothing for a bf16 pass (~4e-3)
FITNESS_RTOL = 1e-4
#: phase-finish direction vs the float64 least-squares Newton step
DIRECTION_COS = 0.999
#: LM loss with the wkv6 kernel vs the kernel-free forward: both legs share
#: every other op, and the kernel-free wkv6 runs its dots at the TPU's
#: default precision, one bf16 pass (~2e-3 per product), averaged over
#: the batch's 64 tokens
LM_RTOL = 2e-3
#: the served search commits this many iterations (each one is ~3k leases
#: through the server at the paper's fleet size)
SERVED_ITERATIONS = 2


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


# -- the deployment ------------------------------------------------------------

def paper_problem(n_stars=None, n_quad: int = 4096, n_hosts=None, m=None,
                  iterations: int = 3, seed: int = 3):
    """stripe79 under ``paper_anm.CONFIG``: (stripe, f_batch, spec).
    Arguments left at None take the paper's values; the CPU test shrinks
    them.  The start point is the stripe's truth perturbed by N(0, 0.2)."""
    from repro.configs import paper_anm
    from repro.core.engine import AnmConfig
    from repro.core.grid import GridConfig
    from repro.core.orchestrator.director import SearchSpec
    from repro.data import sdss

    pc = paper_anm.CONFIG
    n_stars = pc.n_stars if n_stars is None else n_stars
    m = pc.regression_points if m is None else m
    stripe = sdss.make_stripe("stripe79", n_stars=n_stars, n_quad=n_quad,
                              seed=79)
    f_batch, _ = sdss.make_fitness(stripe)
    rng = np.random.default_rng(seed)
    x0 = np.clip(stripe.truth + rng.normal(0, 0.2, 8).astype(np.float32),
                 sdss.LO, sdss.HI)
    fleet = GridConfig(n_hosts=pc.n_hosts if n_hosts is None else n_hosts,
                       failure_prob=pc.host_failure_prob,
                       malicious_prob=pc.host_malicious_prob, seed=seed)
    spec = SearchSpec(
        name="stripe79", x0=np.asarray(x0, np.float64),
        lo=np.asarray(sdss.LO, np.float64),
        hi=np.asarray(sdss.HI, np.float64),
        step=np.asarray(sdss.DEFAULT_STEP, np.float64),
        anm=AnmConfig(m_regression=m, m_line_search=m,
                      alpha_min=pc.alpha_min, alpha_max=pc.alpha_max,
                      max_iterations=iterations),
        grid=fleet, engine_seed=seed,
        validation_quorum=pc.validation_quorum)
    return stripe, f_batch, spec


# -- phase 2: fitness against an independent float64 likelihood ---------------

def reference_nll(points, stars, quad, lo_corner, hi_corner,
                  chunk: int = 32) -> np.ndarray:
    """Mean negative log-likelihood of the stream + halo mixture, float64
    NumPy, one value per row of ``points``.  Written from the model's
    definition, not from ``sdss.log_likelihood``: the tube distance is the
    norm of the perpendicular vector, not |rel|² − along²."""
    stars = np.asarray(stars, np.float64)
    quad = np.asarray(quad, np.float64)
    vol = float(np.prod(np.asarray(hi_corner, np.float64)
                        - np.asarray(lo_corner, np.float64)))
    out = []
    for p in np.array_split(np.asarray(points, np.float64),
                            max(1, -(-len(points) // chunk))):
        eps, cx, cy, cz, th, ph, lsig, q = (p[:, i, None] for i in range(8))
        w = 1.0 / (1.0 + np.exp(-eps))
        sig2 = np.exp(2.0 * lsig)
        axis = np.stack([np.sin(th) * np.cos(ph), np.sin(th) * np.sin(ph),
                         np.cos(th)], axis=-1)                    # (P, 1, 3)
        center = np.stack([cx, cy, cz], axis=-1)                  # (P, 1, 3)

        def halo(x):
            r2 = x[..., 0] ** 2 + x[..., 1] ** 2 + (x[..., 2] / q) ** 2
            return (r2 + 0.25) ** -1.5

        def tube(x):
            rel = x[None] - center
            perp = rel - np.sum(rel * axis, -1, keepdims=True) * axis
            return np.exp(-0.5 * np.sum(perp * perp, -1) / sig2)

        z_halo = np.maximum(np.mean(halo(quad[None]), -1, keepdims=True)
                            * vol, 1e-12)
        z_tube = np.maximum(np.mean(tube(quad), -1, keepdims=True) * vol,
                            1e-12)
        pdf = ((1.0 - w) * halo(stars[None]) / z_halo
               + w * tube(stars) / z_tube)
        out.append(-np.mean(np.log(np.maximum(pdf, 1e-30)), -1))
    return np.concatenate(out)


def phase_fitness(stripe, f_batch, n_points: int = 1024,
                  seed: int = 0) -> dict:
    """One ``n_points`` bucket of the device fitness, drawn uniformly over
    the search box, against ``reference_nll``."""
    from repro.data import sdss

    rng = np.random.default_rng(seed)
    pts = rng.uniform(sdss.LO, sdss.HI, (n_points, 8)).astype(np.float32)
    t0 = time.perf_counter()
    ys = np.asarray(f_batch(pts))
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = reference_nll(pts, stripe.stars, stripe.quad, sdss.WEDGE_LO,
                        sdss.WEDGE_HI)
    t_ref = time.perf_counter() - t0
    check(ys.shape == (n_points,), f"fitness shape {ys.shape}")
    check(bool(np.all(np.isfinite(ys))), "non-finite fitness in the bucket")
    err = rel_err(ys, ref)
    log(f"[fitness] {n_points} points x {len(stripe.stars)} stars: max rel "
        f"err {err:.3e} (limit {FITNESS_RTOL:g}); first call incl. compile "
        f"{t_dev:.3f}s, float64 reference {t_ref:.1f}s")
    check(err <= FITNESS_RTOL,
          f"fitness max rel err {err:.3e} > {FITNESS_RTOL:g}")
    return {"max_rel_err": err}


# -- phase 3: the phase-finish fit against float64 least squares --------------

def _design(deltas):
    n = deltas.shape[1]
    iu, ju = np.triu_indices(n, k=1)
    return np.concatenate([np.ones((len(deltas), 1)), deltas,
                           0.5 * deltas ** 2, deltas[:, iu] * deltas[:, ju]],
                          axis=1)


def _mad_keep(v, k: float = 8.0):
    med = np.median(v)
    mad = np.median(np.abs(v - med)) + 1e-12
    return np.abs(v - med) <= k * 1.4826 * mad


def reference_direction(deltas, ys, damping: float) -> np.ndarray:
    """Float64 NumPy twin of the robust phase-finish: value-MAD guard,
    weighted least squares (``lstsq``), residual-MAD guard, refit, then the
    eigenvalue-shifted Newton step."""
    d = np.asarray(deltas, np.float64)
    y = np.asarray(ys, np.float64)
    n = d.shape[1]
    x = _design(d)
    iu, ju = np.triu_indices(n, k=1)

    def fit(keep):
        beta = np.linalg.lstsq(x[keep], y[keep], rcond=None)[0]
        h = np.zeros((n, n))
        h[iu, ju] = beta[2 * n + 1:]
        h = h + h.T + np.diag(beta[n + 1:2 * n + 1])
        return beta, h

    keep = _mad_keep(y)
    beta, h = fit(keep)
    pred = x @ beta
    keep &= _mad_keep(y - pred)
    beta, h = fit(keep)
    g = beta[1:n + 1]
    evals, evecs = np.linalg.eigh(h)
    lam = max(damping, damping - float(evals.min()))
    return -evecs @ ((evecs.T @ g) / (evals + lam))


def phase_direction(f_batch, spec, m=None, seed: int = 1,
                    require_kernel: bool = True) -> dict:
    """``_regression_direction`` on m box samples around the start point,
    exactly as the engine calls it at phase finish, against
    ``reference_direction`` on the same samples."""
    import jax.numpy as jnp

    from repro.core import engine as E

    cfg = spec.anm
    m = cfg.m_regression if m is None else m
    rng = np.random.default_rng(seed)
    u = rng.uniform(-1.0, 1.0, (m, len(spec.x0)))
    pts = np.clip(spec.x0 + u * spec.step, spec.lo, spec.hi)
    ys = np.asarray(f_batch(pts.astype(np.float32)), np.float64)
    args = (jnp.asarray(pts - spec.x0, jnp.float32),
            jnp.asarray(ys, jnp.float32),
            jnp.asarray(spec.x0, jnp.float32),
            jnp.asarray(spec.lo, jnp.float32),
            jnp.asarray(spec.hi, jnp.float32))
    kw = dict(outlier_guard=cfg.outlier_guard, ridge=cfg.ridge,
              damping=cfg.damping, a_min=cfg.alpha_min, a_max=cfg.alpha_max)
    t0 = time.perf_counter()
    hlo = E._regression_direction.lower(*args, **kw).compile().as_text()
    t_compile = time.perf_counter() - t0
    d, _, _ = E._regression_direction(*args, **kw)
    d = np.asarray(d, np.float64)
    ref = reference_direction(pts - spec.x0, ys.astype(np.float32),
                              cfg.damping)
    check(bool(np.all(np.isfinite(d))), "non-finite Newton direction")
    cos = float(d @ ref / (np.linalg.norm(d) * np.linalg.norm(ref)))
    kernel = "tpu_custom_call" in hlo
    log(f"[direction] m={m}: cosine vs float64 {cos:.7f} (limit "
        f"{DIRECTION_COS}), |d|={np.linalg.norm(d):.4g} vs "
        f"{np.linalg.norm(ref):.4g}; gram kernel in program: {kernel}; "
        f"compile {t_compile:.2f}s")
    check(cos >= DIRECTION_COS, f"direction cosine {cos:.6f} < "
          f"{DIRECTION_COS}")
    check(kernel or not require_kernel,
          "phase-finish program has no tpu_custom_call (gram kernel)")
    return {"cosine": cos, "kernel": kernel}


# -- phases 4 and 5: the search, batched and served ---------------------------

def _committed(engine, f0: float, label: str) -> None:
    hist = [r.best_fitness for r in engine.history]
    check(bool(hist) and all(np.isfinite(hist)),
          f"{label}: committed fitness not finite: {hist}")
    check(hist[-1] < f0, f"{label}: no iteration improved on the "
          f"bootstrap fitness {f0!r}: {hist}")


def phase_batched(backend, spec) -> dict:
    """The pipelined batched grid over ``backend``: warm, then run the
    search with the warmed ladder and count compiles inside the run."""
    from repro.core.substrates.batched_grid import BatchedVolunteerGrid

    n = len(spec.x0)
    max_live = min(spec.grid.n_hosts,
                   BatchedVolunteerGrid.warm_max_bucket(spec.anm.m_regression))
    t0 = time.perf_counter()
    backend.warm(n, max_live)
    f0 = float(backend(spec.x0[None].astype(np.float32))[0])
    t_warm = time.perf_counter() - t0
    compiles = backend.compile_count
    engine = spec.build_engine()
    grid = BatchedVolunteerGrid(None, spec.grid, backend=backend)
    t0 = time.perf_counter()
    st = grid.run(engine)
    wall = time.perf_counter() - t0
    grown = backend.compile_count - compiles
    log(f"[batched] warm {t_warm:.2f}s ({compiles} bucket compiles); "
        f"{engine.iteration} iterations in {wall:.3f}s wall, "
        f"{st.batched_evals} evaluations in {st.batch_calls} dispatches, "
        f"compiles during run {grown}; bootstrap {f0:.6f} -> "
        f"{[round(r.best_fitness, 6) for r in engine.history]}")
    check(grown == 0, f"batched: {grown} compiles after warm()")
    check(engine.iteration >= spec.anm.max_iterations,
          f"batched: {engine.iteration} of {spec.anm.max_iterations} "
          f"iterations committed")
    _committed(engine, f0, "batched")
    return {"engine": engine, "wall_s": wall, "warm_s": t_warm,
            "evals": st.batched_evals, "dispatches": st.batch_calls}


def phase_served(backend, spec) -> dict:
    """The same search served by ``WorkServer``: the loopback client pool
    sends real request_work/report_result messages, and every fitness
    bucket goes through ``backend``."""
    import dataclasses

    from repro.server.sim import ServerSubstrate

    spec = dataclasses.replace(
        spec, anm=dataclasses.replace(spec.anm,
                                      max_iterations=SERVED_ITERATIONS))
    f0 = float(backend(spec.x0[None].astype(np.float32))[0])
    t0 = time.perf_counter()
    sub = ServerSubstrate(spec, spec.grid, backend, transport="loopback")
    t_warm = time.perf_counter() - t0
    compiles = backend.compile_count
    t0 = time.perf_counter()
    res = sub.run()
    wall = time.perf_counter() - t0
    grown = backend.compile_count - compiles
    engine = res.engines[0]
    c = res.server.counters
    log(f"[served] warm {t_warm:.2f}s; {engine.iteration} iterations in "
        f"{wall:.3f}s wall; {c.messages} messages handled, "
        f"{c.leases_issued} leases granted, {c.nowork_replies} no-work "
        f"replies; {res.pool.evals} evaluations in {res.pool.eval_batches} "
        f"dispatches, compiles during run {grown}; bootstrap {f0:.6f} -> "
        f"{[round(r.best_fitness, 6) for r in engine.history]}")
    check(grown == 0, f"served: {grown} compiles after warm()")
    check(engine.iteration >= 1, "served: no iteration committed")
    _committed(engine, f0, "served")
    return {"engine": engine, "wall_s": wall, "messages": c.messages,
            "leases": c.leases_issued}


# -- phase 6: the LM objective -------------------------------------------------

def lm_points(wl, n_points: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(wl.lo, wl.hi, (n_points, wl.k)).astype(np.float32)


def compiled_text(backend, n_points: int) -> str:
    """HLO of the compiled bucket program ``backend`` runs for
    ``n_points`` lanes of its objective."""
    import jax

    from repro.core.substrates.eval_backend import bucket_size

    kp = bucket_size(n_points, backend.min_bucket)
    n = backend.workload.k
    return backend._eval.lower(
        jax.ShapeDtypeStruct((kp, n + 1), np.float32)).compile().as_text()


def phase_lm(arch: str = "rwkv6-7b", k: int = 8, n_points: int = 16,
             require_kernel: bool = True) -> dict:
    """Losses of ``LmLossEvalBackend`` with the model's Pallas routes
    against the same workload built with ``use_kernels=False``."""
    from repro.core.substrates.lm_loss import (LmLossEvalBackend,
                                               make_lm_workload)

    t0 = time.perf_counter()
    wl = make_lm_workload(arch, k=k, use_kernels=True)
    wl_ref = make_lm_workload(arch, k=k, use_kernels=False)
    pts = lm_points(wl, n_points)
    be = LmLossEvalBackend(wl)
    ys = be(pts)
    ys_ref = LmLossEvalBackend(wl_ref)(pts)
    t_first = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled_text(be, n_points)
    check(bool(np.all(np.isfinite(ys))), f"LM losses not finite: {ys}")
    err = rel_err(ys, ys_ref)
    log(f"[lm] {arch} smoke width, k={k}, {n_points} points: losses "
        f"{ys.min():.6f}..{ys.max():.6f}, max rel err vs use_kernels=False "
        f"{err:.3e} (limit {LM_RTOL:g}); wkv6 kernel in program: {kernel}; "
        f"set-up + first calls incl. compile {t_first:.2f}s")
    check(err <= LM_RTOL, f"LM max rel err {err:.3e} > {LM_RTOL:g}")
    check(kernel or not require_kernel,
          "LM program has no tpu_custom_call (wkv6 kernel)")
    return {"max_rel_err": err, "kernel": kernel}


# -- the cross-chip paths (--chips 4) -----------------------------------------

def phase_pod_mesh(f_batch, spec, n_points: int = 1024,
                   seed: int = 0) -> dict:
    """``PodMeshEvalBackend`` over every device against
    ``InProcessEvalBackend`` on the first: one bucket, then the seeded
    batched-grid search on each."""
    import jax

    from repro.core.engine import identical_trajectories
    from repro.core.substrates.eval_backend import InProcessEvalBackend
    from repro.core.substrates.pod_mesh import PodMeshEvalBackend
    from repro.data import sdss

    pts = np.random.default_rng(seed).uniform(
        sdss.LO, sdss.HI, (n_points, 8)).astype(np.float32)
    pod = PodMeshEvalBackend(f_batch)
    check(pod.n_shards == len(jax.devices()),
          f"pod mesh uses {pod.n_shards} of {len(jax.devices())} devices")
    ref = InProcessEvalBackend(f_batch)
    y_pod, y_ref = pod(pts), ref(pts)
    err = rel_err(y_pod, y_ref)
    log(f"[pod] {pod.n_shards} shards, {len(pts)}-point bucket: "
        f"bit-identical {bool(np.array_equal(y_pod, y_ref))}, max rel err "
        f"{err:.3e}")
    check(err <= FITNESS_RTOL, f"pod bucket rel err {err:.3e}")
    a = phase_batched(pod, spec)["engine"]
    b = phase_batched(ref, spec)["engine"]
    same = identical_trajectories(a, b)
    fa = [r.best_fitness for r in a.history]
    fb = [r.best_fitness for r in b.history]
    log(f"[pod] committed trajectories bit-identical: {same}; "
        f"pod {fa} vs in-process {fb}")
    check(len(fa) == len(fb) and rel_err(fa, fb) <= FITNESS_RTOL,
          "pod and in-process committed fitness disagree")
    return {"bit_identical": same, "bucket_rel_err": err}


def phase_lm_mesh(arch: str = "rwkv6-7b", k: int = 8,
                  n_points: int = 16) -> dict:
    """``LmLossEvalBackend`` on a (data=2, model=2) mesh against
    ``mesh=None``."""
    import jax

    from repro.core.substrates.lm_loss import (LmLossEvalBackend,
                                               make_lm_workload)

    wl = make_lm_workload(arch, k=k, use_kernels=True)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
    pts = lm_points(wl, n_points)
    y_mesh = LmLossEvalBackend(wl, mesh=mesh)(pts)
    y_ref = LmLossEvalBackend(wl)(pts)
    same = bool(np.array_equal(y_mesh, y_ref))
    err = rel_err(y_mesh, y_ref)
    log(f"[lm-mesh] (data=2, model=2) vs one device, {n_points} points: "
        f"bit-identical {same}, max rel err {err:.3e}")
    check(err <= FITNESS_RTOL, f"LM mesh rel err {err:.3e}")
    return {"bit_identical": same, "max_rel_err": err}


# -- entry point ---------------------------------------------------------------

def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths on four chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    info = device_info()
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        print("chip_smoke: no TPU found; this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {info['count']} "
              f"devices", file=sys.stderr)
        return 2

    from repro.core.substrates.eval_backend import InProcessEvalBackend
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[device] compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    stripe, f_batch, spec = paper_problem()
    log(f"[setup] stripe79: {len(stripe.stars)} stars, {len(stripe.quad)} "
        f"quadrature points, {spec.grid.n_hosts} hosts, "
        f"m={spec.anm.m_regression}; {time.perf_counter() - t0:.2f}s")
    if args.chips == 4:
        phase_pod_mesh(f_batch, spec)
        phase_lm_mesh()
    else:
        phase_fitness(stripe, f_batch)
        phase_direction(f_batch, spec)
        backend = InProcessEvalBackend(f_batch)
        phase_batched(backend, spec)
        phase_served(backend, spec)
        phase_lm()
    log(f"[done] all phases passed in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
