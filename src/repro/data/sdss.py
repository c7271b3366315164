"""Synthetic SDSS-like star catalogs + the 8-parameter stream MLE (paper §VI).

The paper fits a Sagittarius tidal-stream model plus a Milky Way background
to 92k–112k stars from SDSS stripes.  We reproduce the *shape* of that
optimization problem in JAX: an 8-parameter mixture likelihood over a 3-D
star catalog —

    params = [eps, cx, cy, cz, theta, phi, sigma, q]
      eps            — logit of the stream mixing fraction
      (cx, cy, cz)   — a point on the stream axis
      (theta, phi)   — stream axis orientation
      sigma          — stream (Gaussian tube) width, log-scale
      q              — background halo flattening

    pdf = (1-w)·bg(x; q)/Z_bg + w·stream(x; c, axis, sigma)/Z_stream

Normalization constants are Monte-Carlo quadratures over the survey wedge
with a quadrature set fixed per dataset, so the likelihood is smooth and
deterministic.  Two datasets ("stripe79", "stripe86") mirror the paper's two
test stripes: different truths, sizes, and seeds.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

N_PARAMS = 8
# search-space bounds (paper: user-specified b_min/b_max)
LO = np.array([-6.0, -4.0, -4.0, -4.0, 0.0, -3.2, -3.0, 0.3], np.float32)
HI = np.array([2.0, 4.0, 4.0, 4.0, 3.2, 3.2, 1.0, 1.6], np.float32)
DEFAULT_STEP = 0.1 * (HI - LO)

WEDGE_LO = np.array([-5.0, -5.0, -5.0], np.float32)
WEDGE_HI = np.array([5.0, 5.0, 5.0], np.float32)


@dataclasses.dataclass(frozen=True)
class Stripe:
    name: str
    stars: np.ndarray          # (n_stars, 3)
    quad: np.ndarray           # (n_quad, 3) fixed quadrature points
    truth: np.ndarray          # (8,) generating parameters


def _axis(theta, phi):
    st, ct = jnp.sin(theta), jnp.cos(theta)
    sp, cp = jnp.sin(phi), jnp.cos(phi)
    return jnp.stack([st * cp, st * sp, ct])


def _bg_density(x, q):
    """Flattened-halo power-law background (Hernquist-like)."""
    r2 = x[..., 0] ** 2 + x[..., 1] ** 2 + (x[..., 2] / q) ** 2
    return (r2 + 0.25) ** -1.5


def _stream_density(x, center, axis, sigma):
    """Gaussian tube around the line {center + t·axis}."""
    rel = x - center
    # full f32: perp2 below cancels |rel|² against along², and the TPU's
    # default one-pass bf16 dot would leave ~3 digits in ``along``
    along = jnp.einsum("...k,k->...", rel, axis,
                       precision=jax.lax.Precision.HIGHEST)
    perp2 = jnp.sum(rel * rel, axis=-1) - along ** 2
    return jnp.exp(-0.5 * perp2 / (sigma ** 2))


def log_likelihood(params: jax.Array, stars: jax.Array, quad: jax.Array) -> jax.Array:
    """Mean negative log-likelihood (LOWER is better — a fitness)."""
    eps, cx, cy, cz, theta, phi, lsig, q = (params[i] for i in range(8))
    w = jax.nn.sigmoid(eps)
    sigma = jnp.exp(lsig)
    center = jnp.stack([cx, cy, cz])
    axis = _axis(theta, phi)
    vol = float(np.prod(WEDGE_HI - WEDGE_LO))

    z_bg = jnp.mean(_bg_density(quad, q)) * vol
    z_st = jnp.mean(_stream_density(quad, center, axis, sigma)) * vol

    p_bg = _bg_density(stars, q) / jnp.maximum(z_bg, 1e-12)
    p_st = _stream_density(stars, center, axis, sigma) / jnp.maximum(z_st, 1e-12)
    pdf = (1.0 - w) * p_bg + w * p_st
    return -jnp.mean(jnp.log(jnp.maximum(pdf, 1e-30)))


def make_stripe(name: str, n_stars: int = 100_000, n_quad: int = 4096,
                seed: int = 0) -> Stripe:
    rng = np.random.default_rng(seed)
    # ground truth (perturbed per stripe)
    truth = np.array([
        rng.uniform(-1.5, -0.5),                     # eps (w ~ 0.2-0.4)
        *rng.uniform(-1.0, 1.0, 3),                  # stream center
        rng.uniform(0.8, 2.2), rng.uniform(-1.5, 1.5),  # theta, phi
        np.log(rng.uniform(0.3, 0.6)),               # log sigma
        rng.uniform(0.6, 1.1),                       # q
    ], np.float32)
    w = 1.0 / (1.0 + np.exp(-truth[0]))
    center, sigma, q = truth[1:4], float(np.exp(truth[6])), float(truth[7])
    th, ph = truth[4], truth[5]
    axis = np.array([np.sin(th) * np.cos(ph), np.sin(th) * np.sin(ph), np.cos(th)])

    n_st = int(n_stars * w)
    n_bg = n_stars - n_st
    # stream stars: along the axis, Gaussian tube around it
    t = rng.uniform(-4, 4, n_st)
    e1 = np.cross(axis, [0.0, 0.0, 1.0])
    if np.linalg.norm(e1) < 1e-6:
        e1 = np.cross(axis, [0.0, 1.0, 0.0])
    e1 /= np.linalg.norm(e1)
    e2 = np.cross(axis, e1)
    rad = rng.normal(0, sigma, (n_st, 2))
    st = center + t[:, None] * axis + rad[:, :1] * e1 + rad[:, 1:] * e2
    # background stars: rejection-sample the flattened halo in the wedge
    bg = []
    while sum(len(b) for b in bg) < n_bg:
        cand = rng.uniform(WEDGE_LO, WEDGE_HI, (4 * n_bg + 1024, 3))
        r2 = cand[:, 0] ** 2 + cand[:, 1] ** 2 + (cand[:, 2] / q) ** 2
        dens = (r2 + 0.25) ** -1.5
        keep = rng.random(len(cand)) < dens / dens.max()
        bg.append(cand[keep])
    bg = np.concatenate(bg)[:n_bg]
    stars = np.concatenate([st, bg]).astype(np.float32)
    stars = np.clip(stars, WEDGE_LO, WEDGE_HI)
    rng.shuffle(stars)
    quad = rng.uniform(WEDGE_LO, WEDGE_HI, (n_quad, 3)).astype(np.float32)
    return Stripe(name=name, stars=stars, quad=quad, truth=truth)


def stripe79(n_stars: int = 100_000) -> Stripe:
    return make_stripe("stripe79", n_stars, seed=79)


def stripe86(n_stars: int = 112_000) -> Stripe:
    return make_stripe("stripe86", n_stars, seed=86)


def make_fitness(stripe: Stripe):
    """Returns (f_batch (m,8)->(m,), f_single (8,)->float) jitted fitness fns."""
    stars = jnp.asarray(stripe.stars)
    quad = jnp.asarray(stripe.quad)

    @jax.jit
    def f_single(p):
        return log_likelihood(p, stars, quad)

    @jax.jit
    def f_batch(ps):
        return jax.vmap(lambda p: log_likelihood(p, stars, quad))(ps)

    return f_batch, f_single
