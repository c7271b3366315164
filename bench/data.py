"""The star catalog a configuration names, made from its seed.

A copy of the program's synthetic SDSS stripe generator (paper §VI): a
Gaussian tube of stream stars around a line, plus halo stars drawn by
rejection from a flattened power law inside the survey wedge, plus a
fixed uniform quadrature set over the wedge.  The benchmark makes the
data itself, so that the program and the reference read the same stars
and neither made them.
"""
from __future__ import annotations

import numpy as np


def make_stripe(n_stars: int, n_quad: int, seed: int, wedge_lo,
                wedge_hi):
    """``(stars (n_stars, 3), quad (n_quad, 3), truth (8,))``, float32."""
    wedge_lo = np.asarray(wedge_lo, np.float32)
    wedge_hi = np.asarray(wedge_hi, np.float32)
    rng = np.random.default_rng(seed)
    truth = np.array([
        rng.uniform(-1.5, -0.5),                        # eps (w ~ 0.2-0.4)
        *rng.uniform(-1.0, 1.0, 3),                     # stream center
        rng.uniform(0.8, 2.2), rng.uniform(-1.5, 1.5),  # theta, phi
        np.log(rng.uniform(0.3, 0.6)),                  # log sigma
        rng.uniform(0.6, 1.1),                          # q
    ], np.float32)
    w = 1.0 / (1.0 + np.exp(-truth[0]))
    center, sigma, q = truth[1:4], float(np.exp(truth[6])), float(truth[7])
    th, ph = truth[4], truth[5]
    axis = np.array([np.sin(th) * np.cos(ph), np.sin(th) * np.sin(ph),
                     np.cos(th)])

    n_st = int(n_stars * w)
    n_bg = n_stars - n_st
    t = rng.uniform(-4, 4, n_st)
    e1 = np.cross(axis, [0.0, 0.0, 1.0])
    if np.linalg.norm(e1) < 1e-6:
        e1 = np.cross(axis, [0.0, 1.0, 0.0])
    e1 /= np.linalg.norm(e1)
    e2 = np.cross(axis, e1)
    rad = rng.normal(0, sigma, (n_st, 2))
    st = center + t[:, None] * axis + rad[:, :1] * e1 + rad[:, 1:] * e2
    bg = []
    while sum(len(b) for b in bg) < n_bg:
        cand = rng.uniform(wedge_lo, wedge_hi, (4 * n_bg + 1024, 3))
        r2 = cand[:, 0] ** 2 + cand[:, 1] ** 2 + (cand[:, 2] / q) ** 2
        dens = (r2 + 0.25) ** -1.5
        keep = rng.random(len(cand)) < dens / dens.max()
        bg.append(cand[keep])
    bg = np.concatenate(bg)[:n_bg]
    stars = np.concatenate([st, bg]).astype(np.float32)
    stars = np.clip(stars, wedge_lo, wedge_hi)
    rng.shuffle(stars)
    quad = rng.uniform(wedge_lo, wedge_hi, (n_quad, 3)).astype(np.float32)
    return stars, quad, truth
