"""Regression-based gradient + Hessian estimation (paper §III, eq. 4–5).

We fit the quadratic surrogate in coordinates CENTERED at x'
    f(x' + δ) ≈ c + g·δ + ½ δᵀ H δ
by least squares over m sampled points.  The paper's eq. (4) uses raw
coordinates, which is numerically ill-conditioned away from the origin; the
centered fit is the same surrogate (exact on quadratics — property-tested).
The paper's eq. (5) flat index `2n+1+ni+j` over-counts the upper triangle;
we use the correct triangular layout.

The normal-equations product XᵀX is the compute hot spot at scale
(m up to ~10⁵, cols = (n²+3n)/2 + 1); kernels/gram.py provides the Pallas
kernel (interpret mode on CPU) and this module the pure-jnp path.
``fit_quadratic`` routes to the kernel automatically once the design matrix
crosses ``GRAM_KERNEL_MIN_ELEMENTS`` — so the one dense hot spot uses the
same code path on every substrate, not only in kernel tests (DESIGN.md §3).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Every dot here runs at full f32 (or f64): on a TPU the default precision
# is one bf16 pass, which leaves ~3 significant digits — too few for
# normal equations whose columns span δ⁰…δ² (the CPU computes f32 either way)
_HI = jax.lax.Precision.HIGHEST

# m·cols threshold above which the fused Pallas XᵀX/Xᵀy kernel is used.
# Below it the plain jnp matmul wins (kernel launch/interpret overhead).
GRAM_KERNEL_MIN_ELEMENTS = 32768


def n_columns(n: int) -> int:
    """1 (const) + n (grad) + n (diag) + n(n-1)/2 (off-diag)."""
    return 1 + 2 * n + (n * (n - 1)) // 2


def min_points(n: int) -> int:
    """Minimum evaluations for the regression to be determined (paper: ≥ n²+n;
    exact column count is smaller because H is symmetric)."""
    return n_columns(n)


def design_matrix(deltas: jax.Array) -> jax.Array:
    """deltas: (m, n) points relative to the center.  Returns X (m, cols)."""
    m, n = deltas.shape
    iu, ju = jnp.triu_indices(n, k=1)
    cols = [jnp.ones((m, 1), deltas.dtype), deltas, 0.5 * deltas * deltas,
            deltas[:, iu] * deltas[:, ju]]
    return jnp.concatenate(cols, axis=1)


def unpack(beta: jax.Array, n: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """beta (cols,) -> (c, gradient (n,), Hessian (n,n))  [paper eq. (5)]."""
    c = beta[0]
    g = beta[1 : n + 1]
    h_diag = beta[n + 1 : 2 * n + 1]
    h_off = beta[2 * n + 1 :]
    iu, ju = jnp.triu_indices(n, k=1)
    H = jnp.zeros((n, n), beta.dtype)
    H = H.at[iu, ju].set(h_off)
    H = H + H.T
    H = H + jnp.diag(h_diag)
    return c, g, H


def fit_quadratic(deltas: jax.Array, ys: jax.Array, weights: jax.Array = None,
                  ridge: float = 1e-8, use_kernel: bool = None):
    """Weighted least squares via normal equations (paper eq. 4).

    deltas: (m, n); ys: (m,); weights: (m,) — 0 drops a sample, which is how
    failed/unreturned/outlier evaluations are excluded without stalling
    (the asynchronous robustness property).  Weights must be non-negative
    (the MAD guard emits a 0/1 mask).
    ``use_kernel=None`` routes XᵀX/Xᵀy through the Pallas gram kernel when
    m·cols ≥ GRAM_KERNEL_MIN_ELEMENTS, else uses plain jnp.
    Returns (c, g (n,), H (n,n)).
    """
    m, n = deltas.shape
    x = design_matrix(deltas.astype(jnp.float64) if deltas.dtype == jnp.float64
                      else deltas.astype(jnp.float32))
    y = ys.astype(x.dtype)
    if use_kernel is None:
        # the kernel accumulates in f32; never auto-route a float64 fit
        use_kernel = (x.dtype == jnp.float32
                      and x.shape[0] * x.shape[1] >= GRAM_KERNEL_MIN_ELEMENTS)
    if use_kernel:
        from repro.kernels import ops
        if weights is not None:
            sw = jnp.sqrt(jnp.maximum(weights.astype(x.dtype), 0.0))
            gram, rhs = ops.gram(x * sw[:, None], y * sw)
        else:
            gram, rhs = ops.gram(x, y)
        gram = gram.astype(x.dtype)
        rhs = rhs.astype(x.dtype)
    else:
        xw = x * weights.astype(x.dtype)[:, None] if weights is not None else x
        gram = jnp.matmul(xw.T, x, precision=_HI)     # (cols, cols)
        rhs = jnp.matmul(xw.T, y, precision=_HI)
    # scale-aware ridge keeps the solve stable when columns differ in magnitude
    diag = jnp.diagonal(gram)
    lam = ridge * jnp.maximum(jnp.max(diag), 1.0)
    beta = jnp.linalg.solve(gram + lam * jnp.eye(x.shape[1], dtype=x.dtype), rhs)
    return unpack(beta, n)


def fit_quadratic_robust(deltas: jax.Array, ys: jax.Array,
                         ridge: float = 1e-8, use_kernel: bool = None):
    """Two-pass robust fit: value-MAD guard -> fit -> residual-MAD guard ->
    refit.  A malicious fitness that stays inside the natural spread of the
    sampling box (e.g. the sign-safe lie ``y - (|y|+1)·u``) passes a MAD
    test on raw values, but sits far off the local quadratic surface — the
    residual pass catches exactly those.  Weights are 0/1 masks, so a clean
    sample set refits to the identical surrogate."""
    w = mad_outlier_weights(ys)
    c, g, H = fit_quadratic(deltas, ys, w, ridge, use_kernel)
    pred = c + jnp.matmul(deltas, g, precision=_HI) + \
        0.5 * jnp.einsum("mi,ij,mj->m", deltas, H, deltas, precision=_HI)
    w2 = w * mad_outlier_weights(ys - pred)
    return fit_quadratic(deltas, ys, w2, ridge, use_kernel)


def mad_outlier_weights(ys: jax.Array, k: float = 8.0) -> jax.Array:
    """Median-absolute-deviation outlier mask — drops malicious/corrupt fitness
    values before the fit (robustness guard; see DESIGN.md §2)."""
    finite = jnp.isfinite(ys)
    safe = jnp.where(finite, ys, jnp.nanmedian(jnp.where(finite, ys, jnp.nan)))
    med = jnp.median(safe)
    mad = jnp.median(jnp.abs(safe - med)) + 1e-12
    ok = jnp.abs(safe - med) <= k * 1.4826 * mad
    return (finite & ok).astype(ys.dtype)


def newton_direction(g: jax.Array, H: jax.Array, damping: float = 1e-6) -> jax.Array:
    """d = -(H + λI)⁻¹ g  (paper eq. 3), with eigenvalue-shift damping so the
    direction is a descent direction even for indefinite H."""
    evals, evecs = jnp.linalg.eigh(H)
    lam = jnp.maximum(damping, damping - jnp.min(evals))
    inv = 1.0 / (evals + lam)
    return -jnp.matmul(evecs * inv[None, :],
                       jnp.matmul(evecs.T, g, precision=_HI), precision=_HI)
