"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips
(TPU v5e pod).  Multi-pod: (pod=2, data=16, model=16) = 512 chips, with the
"pod" axis acting as an outer data-parallel axis across the DCN/ICI boundary.
"""
from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "on the CPU, set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "jax is imported")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests/examples (axes preserved)."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
