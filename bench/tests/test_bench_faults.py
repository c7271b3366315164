"""The comparison that decides ``correct`` fails what it must fail.

The control (the references one precision down, bfloat16, in the
program's place) must read above every limit; and a run with the timed
path broken underneath must come out not correct, once for each fault the
cells can have: an answer altered where it is produced, half of the stars
left out of the mean, and a phase-finish that leaves the search where it
was.  (There is no exchange between chips to leave out: every cell takes
one chip.)
"""
import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.tests.tiny import ROOT, cell_config, run_tiny

SPEC = harness.load_benchmark(ROOT)
CELLS = {w["name"]: w for w in SPEC["workloads"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails_and_program_passes(cell):
    cfg = cell_config(cell)
    session = harness.Session(cfg)
    try:
        res = control.collect(session,
                              harness.traffic_of(CELLS[cell]["traffic"]),
                              [11], 1.5, 1, log=lambda m: None)
    finally:
        session.close()
    (prog,), (ctl,) = res["program"], res["control"]
    for k, limit in cfg["limits"].items():
        assert prog[k] < limit < ctl[k], (k, prog[k], limit, ctl[k])


def _altered(log_likelihood):
    def f(params, stars, quad):
        return log_likelihood(params, stars, quad) * (1.0 + 1e-3)
    return f


def _half_the_stars(log_likelihood):
    def f(params, stars, quad):
        return log_likelihood(params, stars[::2], quad)
    return f


@pytest.mark.parametrize("fault", [_altered, _half_the_stars])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_fitness_is_not_correct(monkeypatch, cell, fault):
    from repro.data import sdss
    monkeypatch.setattr(sdss, "log_likelihood", fault(sdss.log_likelihood))
    line = run_tiny(cell)
    assert not line["correct"]
    assert line["checks"]["fitness_rel_err"]["value"] > \
        line["checks"]["fitness_rel_err"]["limit"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_finish_that_leaves_the_search_unchanged_is_not_correct(monkeypatch,
                                                               cell):
    from repro.core import engine
    real = engine._regression_direction

    def stuck(*args, **kw):
        d, a_lo, a_hi = real(*args, **kw)
        return jnp.zeros_like(d), a_lo, a_hi
    monkeypatch.setattr(engine, "_regression_direction", stuck)
    line = run_tiny(cell)
    assert not line["correct"]
    assert line["checks"]["direction_gap"]["value"] == 1.0
