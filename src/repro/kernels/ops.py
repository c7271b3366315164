"""Jit'd public wrappers around the Pallas kernels.

Handles GQA head expansion, MXU padding, layout moves and the
interpret-on-CPU switch (the kernels target TPU; on this CPU container they
are validated in interpret mode against kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import compat, ref
from repro.kernels import flash_attention as _fa
from repro.kernels import gram as _gram
from repro.kernels import wkv6 as _wkv6


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: (B, S, Hq, D); k/v: (B, S, Hkv, D)  ->  (B, S, Hq, D).

    GQA: q heads are grouped onto kv heads (Hq % Hkv == 0).
    """
    if interpret is None:
        interpret = compat.interpret_default()
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    # (B,S,H,D) -> (B*H, S, D), with q grouped by kv head
    qg = q.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4).reshape(b * hkv * g, s, d)
    kg = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * hkv * g, s, d)
    vg = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * hkv * g, s, d)

    qg, pad_d = _pad_to(qg, 2, 128)
    kg, _ = _pad_to(kg, 2, 128)
    vg, _ = _pad_to(vg, 2, 128)
    bq = min(block_q, s)
    bk = min(block_k, s)
    # scale must reflect the true head dim, not the padded one
    scale_fix = ((d + pad_d) / d) ** 0.5
    out = _fa.flash_attention(qg * scale_fix, kg, vg, causal=causal,
                              block_q=bq, block_k=bk, window=window,
                              interpret=interpret)
    if pad_d:
        out = out[..., :d]
    return out.reshape(b, hkv, g, s, d).transpose(0, 3, 1, 2, 4).reshape(b, s, hq, d)


def wkv6(r, k, v, lw, u, *, chunk: int = 256, interpret: bool | None = None):
    """r,k,v,lw: (B, T, H, K); u: (H, K) -> (B, T, H, K) — model layout."""
    if interpret is None:
        interpret = compat.interpret_default()
    b, t, h, kk = r.shape
    to_k = lambda a: a.transpose(0, 2, 1, 3)            # (B,H,T,K)
    c = min(chunk, t)
    while t % c:
        c -= 1
    out = _wkv6.wkv6(to_k(r), to_k(k), to_k(v), to_k(lw), u, chunk=c,
                     interpret=interpret)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Routed model hot paths (DESIGN.md §11): the model's attention and wkv6
# blocks call these when ``ModelConfig.use_kernels`` is set, and
# ``compat.route_pallas`` picks Pallas (TPU) or the pure-jnp ref oracle
# (CPU fallback) at trace time.  Both legs take MODEL layout tensors, so
# the caller never handles layout or GQA expansion.
# ---------------------------------------------------------------------------

def routed_attention(q, k, v, *, causal: bool = True, window: int = 0,
                     pallas: bool | None = None):
    """q: (B, S, Hq, D); k/v: (B, S, Hkv, D) -> (B, S, Hq, D).

    Contiguous causal/sliding-window prefill attention only (positions are
    implicit ``arange`` — exactly the loss/train forward's case); decode
    and packed-position paths stay on the dense mask in models/layers.py.
    """
    if compat.route_pallas(pallas):
        return flash_attention(q, k, v, causal=causal, window=window)
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    # GQA expansion ordered exactly like flash_attention's grouping:
    # q head h serves kv head h // g
    kf = jnp.repeat(k, g, axis=2) if g > 1 else k
    vf = jnp.repeat(v, g, axis=2) if g > 1 else v
    out = ref.attention_ref(q.transpose(0, 2, 1, 3), kf.transpose(0, 2, 1, 3),
                            vf.transpose(0, 2, 1, 3), causal=causal,
                            window=window)
    return out.transpose(0, 2, 1, 3)


def routed_wkv6(r, k, v, lw, u, *, chunk: int = 256,
                pallas: bool | None = None):
    """r,k,v,lw: (B, T, H, K); u: (H, K) -> (B, T, H, K) — model layout.

    Returns the mixed output only (no final recurrent state): the routed
    path serves loss/train forwards, where the state is discarded; decode
    and prefill-into-cache keep ``models/ssm.py``'s chunked scan.
    """
    if compat.route_pallas(pallas):
        return wkv6(r, k, v, lw, u, chunk=chunk)
    return ref.wkv6_ref(r, k, v, lw, u)[0]


def gram(x, y, *, block_m: int = 512, interpret: bool | None = None):
    """x: (m, c); y: (m,) -> (XᵀX (c,c), Xᵀy (c,)) in f32.

    Pads cols to a multiple of 128 and rows to a multiple of block_m
    (zero rows contribute nothing to either product).
    """
    if interpret is None:
        interpret = compat.interpret_default()
    m, c = x.shape
    x, pad_c = _pad_to(x, 1, 128)
    bm = min(block_m, 8 * 128)
    xp, _ = _pad_to(x, 0, bm)
    yp, _ = _pad_to(y, 0, bm)
    g, r = _gram.gram(xp, yp, block_m=bm, interpret=interpret)
    if pad_c:
        g = g[:c, :c]
        r = r[:c]
    return g, r
