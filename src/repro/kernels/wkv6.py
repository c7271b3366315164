"""RWKV6 WKV recurrence as a Pallas TPU kernel.

TPU adaptation: the CUDA kernel parallelizes over (batch, head) thread
blocks with registers holding the (K,V) state; here (batch, head) are
parallel grid axes, time is a sequential grid axis in chunks, and the state
matrix lives in VMEM scratch persisting across time chunks.  Within a chunk
the time loop is a fori_loop over VMEM-resident slices — outer products and
the r·S contraction map to the VPU/MXU.

Layout: (B, H, T, K) so the (T, K) tile is the VMEM block.
Grid: (B, H, n_time_chunks) — last axis sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr,
                 *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    kk = u_ref.shape[-1]
    u_col = u_ref[...].astype(jnp.float32).reshape(kk, 1)   # (K, 1)

    def step(t, _):
        rt = r_ref[0, 0, t, :].astype(jnp.float32)[None, :]  # (1, K)
        kt = k_ref[0, 0, t, :].astype(jnp.float32)[None, :]
        vt = v_ref[0, 0, t, :].astype(jnp.float32)[None, :]
        wt = jnp.exp(lw_ref[0, 0, t, :].astype(jnp.float32))[:, None]  # (K,1)
        kv = kt.T @ vt                                  # (K, V) outer product
        s = s_scr[...]
        o = rt @ (s + u_col * kv)                       # (1, V)
        o_ref[0, 0, t, :] = o[0].astype(o_ref.dtype)
        s_scr[...] = wt * s + kv
        return ()

    jax.lax.fori_loop(0, chunk, step, ())


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, lw, u, *, chunk: int = 256, interpret: bool = False):
    """r,k,v,lw: (B, H, T, K); u: (H, K).  Returns o: (B, H, T, K).

    lw is the per-step log decay (<= 0).  Semantics match ref.wkv6_ref.
    """
    b, h, t, kk = r.shape
    assert t % chunk == 0, (t, chunk)
    grid = (b, h, t // chunk)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    # The time loop reads and writes one row at a dynamic sublane offset,
    # which Mosaic lowers for 32-bit rows only (a packed bf16 row needs an
    # offset it can prove aligned), so the blocks travel in f32 — the
    # kernel computes in f32 either way.
    dtype = r.dtype
    r, k, v, lw = (a.astype(jnp.float32) for a in (r, k, v, lw))

    time_spec = pl.BlockSpec((1, 1, chunk, kk), lambda bi, hi, ti: (bi, hi, ti, 0))
    # u enters as (H, 1, K): Mosaic tiles the last two block dims, and a
    # (1, K) block of an (H, K) array is refused unless H == 1 — (1, K)
    # of a (1, K) trailing slab is the full extent and always tiles
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[time_spec, time_spec, time_spec, time_spec,
                  pl.BlockSpec((1, 1, kk), lambda bi, hi, ti: (hi, 0, 0))],
        out_specs=time_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, kk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, lw, u.reshape(h, 1, kk))
    return out.astype(dtype)
