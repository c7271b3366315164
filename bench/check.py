"""Whether what the window produced is correct.

Four numbers, each against a limit kept in the configuration file
(``limits``); ``PERF.md`` gives the readings each limit was set from.

``fitness_rel_err``   the device fitness: a sample, drawn from the seed, of
                      the lanes the window's buckets returned, at the
                      timed bucket widths, against ``reference.nll`` (a
                      malicious lane against ``reference.lie`` of it);
``direction_gap``     the phase-finish: over every Newton direction the
                      engine computed in the window, the median of
                      1 - cos(d, d_ref), ``d_ref`` from ``reference.direction``
                      on the same samples.  The median, because a phase
                      whose fitted Hessian is indefinite is shifted to the
                      damping, and its step then follows the flattest
                      eigenvector with a length of 1/damping: one rounding
                      turns it, in float64 as in float32;
``committed_rel_err`` what the searches committed: a sample of committed
                      (center, fitness) pairs against ``reference.nll`` at
                      the center, so that a lie the quorum let through, or
                      a fitness that does not belong to its center, shows;
``compiles``          traces and compiles inside the window (limit 0).

A number with nothing to compare (no bucket, no phase-finish, no commit
in the window) reads infinite, and is reported as null: a window that did
no work fails.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: fitness lanes and committed iterates compared per run
LANES = 128
COMMITS = 48


def _rel(a, b, scale) -> float:
    """Largest |a - b| relative to ``scale``, the honest fitness: a lie
    lane's value is near 0 by design, and its error is judged against
    the fitness it misreports."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.size == 0:
        return float("inf")
    return float(np.max(np.abs(a - b)
                        / np.maximum(np.abs(scale), 1e-30)))


def cos_gap(d, ref) -> float:
    """1 - cos(d, ref); a zero or non-finite ``d`` reads 1."""
    nd, nr = np.linalg.norm(d), np.linalg.norm(ref)
    if not (np.isfinite(nd) and nd > 0 and nr > 0):
        return 1.0
    return float(1.0 - d @ ref / (nd * nr))


def _pick(rng, n: int, k: int) -> np.ndarray:
    return np.sort(rng.choice(n, min(n, k), replace=False)) if n else \
        np.zeros(0, np.int64)


def samples(problem, blocks, searches, seed: int):
    """The lanes and commits a run compares, drawn from its seed:
    ``(points, mal_u, values, centers, committed)``."""
    from bench.generator import derived

    rng = np.random.default_rng(derived(seed, 0xC4EC)[0])
    if blocks:
        pts = np.concatenate([b[0] for b in blocks])
        mal = np.concatenate([b[1] for b in blocks])
        ys = np.concatenate([b[2] for b in blocks])
    else:
        pts, mal, ys = np.zeros((0, len(problem.truth))), np.zeros(0), \
            np.zeros(0)
    i = _pick(rng, len(ys), LANES)
    hist = [(h.center, h.best_fitness) for s in searches
            for h in s.engine.history]
    j = _pick(rng, len(hist), COMMITS)
    centers = np.array([hist[t][0] for t in j]).reshape(-1, len(problem.truth))
    committed = np.array([hist[t][1] for t in j], np.float64)
    return pts[i], mal[i], ys[i], centers, committed


def staged(points) -> np.ndarray:
    """Points as the device saw them: rounded to float32."""
    return np.asarray(points, np.float64).astype(np.float32).astype(
        np.float64)


def fitness_values(problem, points, mal_u, nll):
    """``(reported, honest)``: what a host reports for each point (the
    lie for a malicious lane) and the honest fitness, both by ``nll``."""
    from bench import reference
    st = problem.config["stripe"]
    ref = nll(staged(points), problem.stars, problem.quad, st["wedge_lo"],
              st["wedge_hi"])
    return np.where(np.isnan(mal_u), ref, reference.lie(ref, mal_u)), ref


def readings(problem, blocks, finishes, searches, seed: int, *,
             nll=None, direction=None) -> Dict[str, float]:
    """The three compared numbers.  ``nll``/``direction`` default to the
    float64 references; the control passes the value a bfloat16 program
    would have produced in the program's place instead of the program's
    own values (see ``control.py``)."""
    from bench import reference

    damping = problem.config["anm"]["damping"]
    pts, mal, ys, centers, committed = samples(problem, blocks, searches,
                                               seed)
    honest = np.full(len(centers), np.nan)
    want, scale = fitness_values(problem, pts, mal, reference.nll)
    c_ref, _ = fitness_values(problem, centers, honest, reference.nll)
    if nll is not None:
        ys, _ = fitness_values(problem, pts, mal, nll)
        committed, _ = fitness_values(problem, centers, honest, nll)
    d_err = []
    for deltas, fys, d in finishes:
        deltas = np.asarray(deltas, np.float64)
        fys = np.asarray(fys, np.float64)
        ref = reference.direction(deltas, fys, damping)
        got = (np.asarray(d, np.float64) if direction is None
               else direction(deltas, fys, damping))
        d_err.append(cos_gap(got, ref))
    return {"fitness_rel_err": _rel(ys, want, scale),
            "direction_gap": float(np.median(d_err)) if d_err
            else float("inf"),
            "committed_rel_err": _rel(committed, c_ref, c_ref)}


def compare(problem, blocks, finishes, searches, compiles: int,
            seed: int) -> Dict[str, dict]:
    """Every compared number with its limit and verdict."""
    limits = problem.config["limits"]
    out = {k: {"value": v if np.isfinite(v) else None, "limit": limits[k],
               "ok": bool(v <= limits[k])}
           for k, v in readings(problem, blocks, finishes, searches,
                                seed).items()}
    out["compiles"] = {"value": compiles, "limit": 0, "ok": compiles == 0}
    return out
