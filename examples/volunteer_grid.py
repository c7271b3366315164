"""FGDO on a simulated volunteer grid — the paper's full system (§V–§VI).

A 256-host heterogeneous, faulty, partly-malicious grid fits the
8-parameter synthetic SDSS stream model asynchronously: work generated on
demand, phases advance on the first m results, the best line-search point
is quorum-validated before being committed.

Both grid substrates drive the SAME AnmEngine state machine (DESIGN.md §1):
the per-event simulator through the BOINC-style FgdoAnmServer adapter, and
the vectorized batched grid directly — the second act of this script reruns
the problem at 4096 hosts with one jitted f_batch call per tick.

The batched acts take the PR-3 async path's knobs on the command line, so
the example exercises the pipelined tick loop and both evaluation backends
without edits:

    PYTHONPATH=src python examples/volunteer_grid.py
    PYTHONPATH=src python examples/volunteer_grid.py --no-pipelined
    PYTHONPATH=src python examples/volunteer_grid.py \
        --backend pod_mesh --pipeline-depth 6
"""
import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.configs import paper_anm
from repro.core.anm import AnmConfig
from repro.core.engine import AnmEngine, identical_trajectories
from repro.core.fgdo import FgdoAnmServer
from repro.core.grid import GridConfig, VolunteerGrid
from repro.core.substrates.batched_grid import BatchedVolunteerGrid
from repro.core.substrates.eval_backend import InProcessEvalBackend
from repro.core.substrates.pod_mesh import PodMeshEvalBackend
from repro.data import sdss


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pipelined", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pipelined tick loop (DESIGN.md §7) for the "
                         "batched acts; --no-pipelined collects every "
                         "bucket synchronously")
    ap.add_argument("--pipeline-depth", type=int, default=4,
                    help="max in-flight tick buckets when pipelined")
    ap.add_argument("--backend", default="in_process",
                    choices=["in_process", "pod_mesh"],
                    help="evaluation backend for act 2 (act 3 runs the "
                         "OTHER backend for the parity comparison)")
    args = ap.parse_args()
    pc = paper_anm.smoke()
    stripe = sdss.make_stripe("stripe79", n_stars=6_000, seed=79)
    _, f_single = sdss.make_fitness(stripe)
    rng = np.random.default_rng(1)
    x0 = np.clip(stripe.truth + rng.normal(0, 0.2, 8).astype(np.float32),
                 sdss.LO, sdss.HI)
    f0 = float(f_single(jnp.asarray(x0)))
    print(f"start fitness {f0:.5f}; truth "
          f"{float(f_single(jnp.asarray(stripe.truth))):.5f}")

    server = FgdoAnmServer(
        x0, sdss.LO, sdss.HI, sdss.DEFAULT_STEP,
        AnmConfig(m_regression=128, m_line_search=128, max_iterations=8),
        seed=3, validation_quorum=pc.validation_quorum)
    grid = VolunteerGrid(
        lambda p: float(f_single(jnp.asarray(p, jnp.float32))),
        GridConfig(n_hosts=256, base_eval_time=3600.0, speed_sigma=1.0,
                   failure_prob=0.1, malicious_prob=0.03, seed=5))
    gstats = grid.run(server)

    print(f"converged to {server.best_fitness:.5f} in {server.iteration} "
          f"iterations / {gstats.sim_time / 3600:.1f} simulated hours")
    print(f"grid: {gstats.completed} results ({gstats.failed} lost, "
          f"{gstats.corrupted} corrupted), {server.stats.stale} stale "
          f"discarded, {server.stats.validations_failed} malicious bests "
          f"rejected by quorum")
    for rec in server.history:
        print(f"  iter {rec.iteration}: best={rec.best_fitness:.5f} "
              f"alpha={rec.best_alpha:.2f}")

    # -- act 2: the same engine on the vectorized 4096-host substrate --------
    f_batch, _ = sdss.make_fitness(stripe)
    backends = {"in_process": lambda: InProcessEvalBackend(f_batch),
                "pod_mesh": lambda: PodMeshEvalBackend(f_batch)}
    backend2 = backends[args.backend]()
    engine = AnmEngine(x0, sdss.LO, sdss.HI, sdss.DEFAULT_STEP,
                       AnmConfig(m_regression=128, m_line_search=128,
                                 max_iterations=8),
                       seed=3, validation_quorum=pc.validation_quorum)
    t0 = time.perf_counter()
    bstats = BatchedVolunteerGrid(
        None, GridConfig(n_hosts=4096, base_eval_time=3600.0,
                         speed_sigma=1.0, failure_prob=0.1,
                         malicious_prob=0.03, seed=5),
        backend=backend2, pipelined=args.pipelined,
        pipeline_depth=args.pipeline_depth).run(engine)
    wall = time.perf_counter() - t0
    print(f"batched grid (4096 hosts, {args.backend} backend, "
          f"{'pipelined' if args.pipelined else 'sync'}): "
          f"{engine.best_fitness:.5f} in "
          f"{engine.iteration} iterations / {bstats.sim_time / 3600:.1f} "
          f"simulated hours — {bstats.batch_calls} fitness batches "
          f"(mean {bstats.batched_evals / max(bstats.batch_calls, 1):.0f} "
          f"points each), {wall:.1f}s wall")
    print(f"  ticks (DESIGN.md §7): {bstats.ticks}, "
          f"pipeline depth {bstats.max_in_flight}, "
          f"{bstats.spec_blocks} speculative blocks "
          f"({bstats.spec_discarded} discarded)")

    # -- act 3: the same grid through the OTHER backend ----------------------
    # (DESIGN.md §6 — on this CPU the pod mesh degenerates to the available
    # devices; tests/test_sigkill_restore.py runs the real 16x16
    # partitioning on 512 forced host devices.  Same seed => bit-identical
    # iterates, on either backend, pipelined or not.)
    other = "pod_mesh" if args.backend == "in_process" else "in_process"
    backend3 = backends[other]()
    engine2 = AnmEngine(x0, sdss.LO, sdss.HI, sdss.DEFAULT_STEP,
                        AnmConfig(m_regression=128, m_line_search=128,
                                  max_iterations=8),
                        seed=3, validation_quorum=pc.validation_quorum)
    BatchedVolunteerGrid(
        None, GridConfig(n_hosts=4096, base_eval_time=3600.0,
                         speed_sigma=1.0, failure_prob=0.1,
                         malicious_prob=0.03, seed=5),
        backend=backend3, pipelined=args.pipelined,
        pipeline_depth=args.pipeline_depth).run(engine2)
    identical = identical_trajectories(engine, engine2)
    print(f"{other} backend: {engine2.best_fitness:.5f} — iterates "
          f"{'bit-identical to' if identical else 'DIVERGED from'} "
          f"the {args.backend} backend")


if __name__ == "__main__":
    main()
