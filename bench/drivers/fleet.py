"""Portfolios of multi-start searches sharing one fleet through the
orchestrator: ``FleetScheduler`` gives each search a fixed sub-fleet and
steps one tick of every live search a round; with ``coalesce`` the
coalescer folds a round's tick blocks into one dispatch (mix keys:
``searches``, ``coalesce``, ``jitter``).

Each member of the mix is one portfolio: the member's search is the start
of ``multi_start_specs``, its fleet is the whole shared fleet, and the
portfolio's ``searches`` searches are admitted together.  A search leaves
the rounds when its committed best fitness reaches the target or its
engine stops; the next portfolio starts when all of them have left."""
import time
from typing import List, Optional

import numpy as np

from bench.generator import SET_SEED, SearchLog, members, search_spec


class Driver:

    def __init__(self, problem, mix, seed, backend):
        self.problem, self.mix, self.seed = problem, mix, seed
        self.backend = backend
        self.searches: List[SearchLog] = []

    def portfolio(self, member: int):
        """``(scheduler, specs)``: member ``member``'s portfolio on a new
        scheduler over the backend."""
        from repro.core.orchestrator import FleetScheduler, multi_start_specs

        spec = search_spec(self.problem, SET_SEED, member)
        scheduler = FleetScheduler(self.backend, spec.grid,
                                   coalesce=self.mix["coalesce"])
        specs = multi_start_specs(
            scheduler, spec.x0, spec.lo, spec.hi, spec.step, spec.anm,
            self.mix["searches"], seed=spec.engine_seed,
            jitter=self.mix["jitter"],
            validation_quorum=spec.validation_quorum)
        return scheduler, specs

    def warm(self) -> None:
        # every member's portfolio has the same sub-fleets and phase
        # sizes, so one warms the ladder of all: with coalescing up to the
        # sum of the sub-fleets' bounds
        scheduler, specs = self.portfolio(self.mix["members"][0])
        scheduler.warm(len(specs[0].x0), specs)

    def run(self, deadline: float) -> dict:
        order = members(self.mix, self.seed)
        while time.perf_counter() < deadline:
            self.searches.extend(self.run_portfolio(
                *self.portfolio(next(order)), deadline, self.problem.target))
        return {}

    def run_portfolio(self, scheduler, specs, deadline: float,
                      target: Optional[float]) -> List[SearchLog]:
        """Admit ``specs`` and step rounds until every search has reached
        ``target`` (None: none retires early) or stopped, or the deadline
        passes; returns the searches' logs in ``specs`` order."""
        live = [scheduler.admit(spec, i) for i, spec in enumerate(specs)]
        logs = [SearchLog(ls.engine) for ls in live]
        log_of = {ls.search_id: log for ls, log in zip(live, logs)}
        while live and time.perf_counter() < deadline:
            stopped = scheduler.round(live)
            for ls in list(live):
                log = log_of[ls.search_id]
                log.hit = (target is not None
                           and ls.engine.best_fitness <= target)
                if log.hit or ls in stopped:
                    live.remove(ls)
                    ls.grid.finish()
                    log.ended = log.hit or ls.engine.done
        for ls in live:                   # the window closed on them
            ls.grid.finish()
        return logs


def solo_parity(driver: Driver, member: int) -> List[dict]:
    """The orchestrator's contract, checked outside any window: member
    ``member``'s portfolio run to its end, then each of its searches alone
    (``SearchSpec.solo_run``) on the same backend.  One row a search:
    whether the committed histories and the ``EngineStats`` are
    bit-identical, the first committed iteration at which they differ
    (None where none does), and the largest relative difference between
    the fitness values both committed."""
    from repro.core.engine import identical_trajectories

    scheduler, specs = driver.portfolio(member)
    logs = driver.run_portfolio(scheduler, specs, float("inf"), None)
    rows = []
    for spec, log in zip(specs, logs):
        solo = spec.solo_run(driver.backend)
        pairs = list(zip(log.engine.history, solo.history))
        a = np.array([r.best_fitness for r, _ in pairs])
        b = np.array([r.best_fitness for _, r in pairs])
        rows.append({
            "search": spec.name,
            "iterations": [log.engine.iteration, solo.iteration],
            "history": identical_trajectories(log.engine, solo),
            "stats": log.engine.stats == solo.stats,
            "first_diff": next(
                (r.iteration for r, q in pairs
                 if r.best_fitness != q.best_fitness
                 or not np.array_equal(r.center, q.center)), None),
            "max_rel_diff": float(np.max(np.abs(a - b) / np.abs(b),
                                         initial=0.0))})
    return rows
