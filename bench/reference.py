"""Plain references for the stream fit, independent of the program.

``nll`` is the mean negative log-likelihood of the stream + halo mixture
in float64 NumPy, written from the model's definition: the tube distance
is the norm of the perpendicular vector, not |rel|^2 - along^2 as the
program computes it.  ``direction`` is the robust phase-finish in float64:
value-MAD guard, least squares, residual-MAD guard, refit, then the
eigenvalue-shifted Newton step.  ``lie`` is the fleet's sign-safe
malicious report, restated from its definition.

``nll_lowp`` and ``direction_lowp`` are the controls: the same references
one precision below the configuration's float32, i.e. bfloat16.  They
exist to show that the comparison in ``check.py`` fails such a program.

Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np


def nll(points, stars, quad, lo_corner, hi_corner,
        chunk: int = 32) -> np.ndarray:
    """Mean negative log-likelihood, one value per row of ``points``."""
    stars = np.asarray(stars, np.float64)
    quad = np.asarray(quad, np.float64)
    vol = float(np.prod(np.asarray(hi_corner, np.float64)
                        - np.asarray(lo_corner, np.float64)))
    points = np.asarray(points, np.float64)
    out = []
    for p in np.array_split(points, max(1, -(-len(points) // chunk))):
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
    return np.concatenate(out) if out else np.zeros(0)


def lie(y, u):
    """A malicious host's report for true fitness ``y`` and draw ``u``:
    under-reports by ``u * (|y| + 1)``."""
    return y - (np.abs(y) + 1.0) * u


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


def direction(deltas, ys, damping: float) -> np.ndarray:
    """Newton direction of the robust quadratic fit to ``ys`` over
    ``deltas`` (offsets from the center), float64."""
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
    keep &= _mad_keep(y - x @ beta)
    beta, h = fit(keep)
    g = beta[1:n + 1]
    evals, evecs = np.linalg.eigh(h)
    lam = max(damping, damping - float(evals.min()))
    return -evecs @ ((evecs.T @ g) / (evals + lam))


# -- controls: the references one precision down --------------------------

def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16)


def direction_lowp(deltas, ys, damping: float) -> np.ndarray:
    """``direction`` with its inputs held in bfloat16, the precision a
    program that stages the fit's samples in bfloat16 would see."""
    return direction(_bf16(deltas).astype(np.float64),
                     _bf16(ys).astype(np.float64), damping)


def nll_lowp(points, stars, quad, lo_corner, hi_corner,
             chunk: int = 32) -> np.ndarray:
    """``nll`` computed in bfloat16 on the default JAX device: every
    array, every intermediate and every mean in bfloat16."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    vol = float(np.prod(np.asarray(hi_corner, np.float64)
                        - np.asarray(lo_corner, np.float64)))

    @jax.jit
    def block(p, stars, quad):
        eps, cx, cy, cz, th, ph, lsig, q = (p[:, i, None] for i in range(8))
        w = jax.nn.sigmoid(eps)
        sig2 = jnp.exp(bf(2.0) * lsig)
        axis = jnp.stack([jnp.sin(th) * jnp.cos(ph),
                          jnp.sin(th) * jnp.sin(ph), jnp.cos(th)], axis=-1)
        center = jnp.stack([cx, cy, cz], axis=-1)

        def halo(x):
            r2 = x[..., 0] ** 2 + x[..., 1] ** 2 + (x[..., 2] / q) ** 2
            return (r2 + bf(0.25)) ** bf(-1.5)

        def tube(x):
            rel = x[None] - center
            perp = rel - jnp.sum(rel * axis, -1, keepdims=True) * axis
            return jnp.exp(bf(-0.5) * jnp.sum(perp * perp, -1) / sig2)

        z_halo = jnp.maximum(jnp.mean(halo(quad[None]), -1, keepdims=True)
                             * bf(vol), bf(1e-12))
        z_tube = jnp.maximum(jnp.mean(tube(quad), -1, keepdims=True)
                             * bf(vol), bf(1e-12))
        pdf = ((bf(1.0) - w) * halo(stars[None]) / z_halo
               + w * tube(stars) / z_tube)
        return -jnp.mean(jnp.log(jnp.maximum(pdf, bf(1e-30))), -1)

    stars_d = jnp.asarray(np.asarray(stars), bf)
    quad_d = jnp.asarray(np.asarray(quad), bf)
    points = np.asarray(points, np.float64)
    n = len(points)
    pad = -n % chunk
    padded = np.concatenate([points, np.repeat(points[-1:], pad, 0)]) \
        if pad and n else points
    out = [np.asarray(block(jnp.asarray(padded[i:i + chunk], bf), stars_d,
                            quad_d), np.float64)
           for i in range(0, len(padded), chunk)]
    return np.concatenate(out)[:n] if out else np.zeros(0)
