"""Searches one at a time through the pipelined batched volunteer grid
over the evaluation backend; the work server is bypassed."""
import time
from typing import List

from bench.generator import SET_SEED, SearchLog, members, search_spec


class Driver:

    def __init__(self, problem, mix, seed, backend):
        self.problem, self.mix, self.seed = problem, mix, seed
        self.backend = backend
        self.searches: List[SearchLog] = []

    def warm(self) -> None:
        from repro.core.substrates.batched_grid import BatchedVolunteerGrid
        spec = search_spec(self.problem, SET_SEED, 0)
        self.backend.warm(len(spec.x0), min(
            spec.grid.n_hosts, BatchedVolunteerGrid.warm_max_bucket(
                max(spec.anm.m_regression, spec.anm.m_line_search))))

    def run(self, deadline: float) -> dict:
        from repro.core.substrates.batched_grid import BatchedVolunteerGrid

        target = self.problem.target
        order = members(self.mix, self.seed)
        while time.perf_counter() < deadline:
            spec = search_spec(self.problem, SET_SEED, next(order))
            engine = spec.build_engine()
            log = SearchLog(engine)
            self.searches.append(log)
            grid = BatchedVolunteerGrid(None, spec.grid, backend=self.backend)
            grid.start(engine)
            while time.perf_counter() < deadline and grid.step():
                if engine.best_fitness <= target:
                    log.hit = True
                    break
            grid.finish()
            log.ended = log.hit or engine.done
        return {}
