"""Fused XᵀX + Xᵀy accumulation for the ANM regression (paper eq. 4).

The regression's normal-equations product is the only dense-compute hot spot
in the paper's method: X is tall-skinny (m up to ~10⁵ sampled evaluations ×
cols = (n²+3n)/2+1).  The kernel streams row-blocks of X through VMEM and
accumulates G += XᵦᵀXᵦ on the MXU into a persistent f32 VMEM scratch tile —
one pass over X, no (m × cols) intermediate in HBM beyond X itself.

ops.py pads cols to a multiple of 128 (MXU lane alignment) and strips after.
Grid: (n_row_blocks,) — sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat


def _gram_kernel(x_ref, y_ref, g_ref, r_ref, g_scr, r_scr):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        r_scr[...] = jnp.zeros_like(r_scr)

    xb = x_ref[...].astype(jnp.float32)                 # (bm, c)
    yb = y_ref[...].astype(jnp.float32)                 # (bm, 1)
    # explicit fp32 contraction: the normal equations need more than the
    # ~3 digits of a one-pass bf16 product
    hi = jax.lax.Precision.HIGHEST
    g_scr[...] += jax.lax.dot_general(xb, xb, (((0,), (0,)), ((), ())),
                                      precision=hi,
                                      preferred_element_type=jnp.float32)
    r_scr[...] += jax.lax.dot_general(xb, yb, (((0,), (0,)), ((), ())),
                                      precision=hi,
                                      preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _emit():
        g_ref[...] = g_scr[...]
        r_ref[...] = r_scr[...]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def gram(x, y, *, block_m: int = 512, interpret: bool = False):
    """x: (m, c) with m % block_m == 0, c MXU-aligned; y: (m,).
    Returns (XᵀX (c,c) f32, Xᵀy (c,) f32)."""
    m, c = x.shape
    assert m % block_m == 0, (m, block_m)
    grid = (m // block_m,)
    g, r = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, c), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((c, c), lambda i: (0, 0)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((c, c), jnp.float32),
                   jax.ShapeDtypeStruct((c, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((c, c), jnp.float32),
                        pltpu.VMEM((c, 1), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, y[:, None])
    return g, r[:, 0]
