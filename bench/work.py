"""Operations and bytes one likelihood evaluation needs, from its shapes.

Counted from the formula, not from what any implementation does; each
arithmetic operation, comparison and transcendental counts as one.

Per star (``STAR_FLOPS``):
  halo density   x^2, y^2, z/q, (z/q)^2, two adds, +0.25, ^-1.5        8
  tube density   rel = x - c (3), rel.axis (5), |rel|^2 (5), along^2,
                 |rel|^2 - along^2, / sigma^2, * -1/2, exp              18
  mixture        two normalisations, two weights, sum, floor, log,
                 accumulate into the mean                                8
Per quadrature point (``QUAD_FLOPS``): both densities (8 + 18) and their
two accumulations, 28.
Per evaluation, once (``LANE_FLOPS``): the sigmoid (4), exp of the width,
sigma^2, the axis (4 trig, 2 products), the two normalisation constants
(mean, volume, floor: 3 each), 1 - w, the final mean and negation: 21.

The least bytes a bucket of ``k`` evaluations must move: the stars and
the quadrature points read once, each lane's parameters and malicious
draw read, each lane's fitness written; float32 throughout.
"""
from __future__ import annotations

STAR_FLOPS = 34
QUAD_FLOPS = 28
LANE_FLOPS = 21
F32 = 4


def flops_per_eval(n_stars: int, n_quad: int) -> int:
    return STAR_FLOPS * n_stars + QUAD_FLOPS * n_quad + LANE_FLOPS


def bucket_bytes(k: int, n_stars: int, n_quad: int, n_params: int) -> int:
    """Least bytes read and written by one bucket of ``k`` lanes."""
    return F32 * (3 * n_stars + 3 * n_quad + k * (n_params + 1) + k)


def least_seconds(k: int, n_stars: int, n_quad: int, n_params: int,
                  peak: dict) -> float:
    """The roofline bound on one bucket: the larger of its operations
    over the peak rate and its bytes over the peak bandwidth."""
    return max(k * flops_per_eval(n_stars, n_quad) / peak["flops_per_s"],
               bucket_bytes(k, n_stars, n_quad, n_params)
               / peak["bytes_per_s"])
