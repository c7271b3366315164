"""The one traffic generator: searches offered to the system under test.

A traffic mix is a data file, ``bench/traffic/<mix>.json``.  Its
``driver`` names the way a user's searches reach the system, one file
each, ``bench/drivers/<driver>.py``, found by name like a metric's reader;
its other keys are that driver's parameters, ``members`` among them for
every driver (see below).  A new way in is a new driver file, never an edit
here:

``grid``    searches one at a time, each on its own ``BatchedVolunteerGrid``
            over the evaluation backend;
``served``  searches served by ``WorkServer`` to the simulated volunteer
            fleet, one connection in virtual time (keys: ``transport``).

A driver module exposes ``Driver(problem, mix, seed, backend)`` with
``warm()``, which compiles every program its window will run, and
``run(deadline) -> dict``, the window itself; ``Driver.searches`` lists a
``SearchLog`` for each search the window started.

Every driver is a closed loop: the next search starts when the last one
ends.  A search ends when its committed best fitness reaches the
configuration's target, or, having missed it, when the engine stops after
``max_iterations``.

Every run offers the same work: the searches the mix lists by index
under ``members``, each with its start point, engine seed and fleet seed
drawn from its index alone (from ``SET_SEED``).  The run's seed only
chooses the order in which they are offered, cycling through them until
the window closes; so two runs differ in which members fall in the
window's last, partial cycle, and not in the searches themselves.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import List

import numpy as np


#: the seed the set's members are drawn from, the same in every run
SET_SEED = 0x5E7


def derived(seed: int, *index: int, n: int = 1) -> List[int]:
    """``n`` 32-bit seeds drawn from the run seed and an index path."""
    ss = np.random.SeedSequence([seed % (1 << 64), *index])
    return [int(v) for v in ss.generate_state(n)]


@dataclasses.dataclass
class SearchLog:
    """One search the window started: its engine, and how it ended."""
    engine: object
    hit: bool = False             # reached the target
    ended: bool = False           # reached the target or gave up


def search_spec(problem, seed: int, index: int, *, n_hosts=None,
                name: str = "search"):
    """Search ``index`` of the set drawn from ``seed``: start at the
    stripe's truth plus a seeded perturbation, engine and fleet seeded
    alike."""
    from repro.core.engine import AnmConfig
    from repro.core.grid import GridConfig
    from repro.core.orchestrator.director import SearchSpec

    cfg = problem.config
    anm, fleet = cfg["anm"], cfg["fleet"]
    s_start, s_engine, s_grid = derived(seed, index, n=3)
    rng = np.random.default_rng(s_start)
    x0 = np.clip(problem.truth.astype(np.float64)
                 + rng.normal(0.0, cfg["assumed"]["start_sigma"],
                              len(problem.truth)),
                 problem.lo, problem.hi)
    grid = GridConfig(n_hosts=fleet["n_hosts"] if n_hosts is None
                      else n_hosts,
                      failure_prob=fleet["failure_prob"],
                      malicious_prob=fleet["malicious_prob"], seed=s_grid)
    return SearchSpec(
        name=f"{name}-{index}", x0=x0, lo=problem.lo, hi=problem.hi,
        step=problem.step,
        anm=AnmConfig(m_regression=anm["m_regression"],
                      m_line_search=anm["m_line_search"],
                      alpha_min=anm["alpha_min"], alpha_max=anm["alpha_max"],
                      damping=anm["damping"],
                      max_iterations=anm["max_iterations"]),
        grid=grid, engine_seed=s_engine,
        validation_quorum=anm["validation_quorum"])


def members(mix: dict, seed: int):
    """The mix's member indices in the order this run offers them,
    cycling without end."""
    rng = np.random.default_rng(derived(seed, 0x0D3)[0])
    return itertools.cycle(int(j) for j in rng.permutation(mix["members"]))
