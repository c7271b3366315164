"""Searches served by the work server to the simulated volunteer fleet,
one connection in virtual time (mix key ``transport``)."""
import dataclasses
import time
from typing import List

from bench.generator import SET_SEED, SearchLog, members, search_spec


class WindowClosed(Exception):
    """Raised inside the served loop when the window's time is up."""


class _UntilDeadline:
    """A client connection that refuses to send once the window closes."""

    def __init__(self, conn, deadline: float):
        self.conn, self.deadline = conn, deadline

    def call(self, msg: dict) -> dict:
        if time.perf_counter() >= self.deadline:
            raise WindowClosed
        return self.conn.call(msg)


class Driver:

    def __init__(self, problem, mix, seed, backend):
        self.problem, self.mix, self.seed = problem, mix, seed
        self.backend = backend
        self.searches: List[SearchLog] = []
        self.counters = {}

    def warm(self) -> None:
        # in-flight unknowns are bounded by the fleet (one lease per
        # host), so the pool's lazy buckets stay on this ladder
        self.backend.warm(len(self.problem.truth),
                          self.problem.config["fleet"]["n_hosts"])

    def run(self, deadline: float) -> dict:
        from repro.server.server import WorkServer
        from repro.server.sim import SimClientPool
        from repro.server.transport import make_transport

        order = members(self.mix, self.seed)
        while time.perf_counter() < deadline:
            spec = search_spec(self.problem, SET_SEED, next(order))
            fleet = spec.grid
            server = WorkServer([spec],
                                lease_timeout=8.0 * fleet.base_eval_time,
                                idle_retry=fleet.idle_retry)
            log = SearchLog(server.engines[0])
            self.searches.append(log)
            transport = make_transport(self.mix["transport"])
            transport.start(server.handle)
            conn = transport.connect()
            pool = SimClientPool(fleet, self.backend)
            try:
                pool.run(_UntilDeadline(conn, deadline))
                log.ended = True
            except WindowClosed:
                pass
            finally:
                conn.close()
                transport.stop()
            log.hit = log.engine.best_fitness <= self.problem.target
            c = dataclasses.asdict(server.counters)
            for k, v in c.items():
                self.counters[k] = self.counters.get(k, 0) + v
        return {"server": dict(self.counters)}
