"""Kernel-side platform decisions, kept in one place.

Every kernel in this package takes its Mosaic params class and its
interpret/route defaults from here.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams


def interpret_default() -> bool:
    """The kernels target TPU; on CPU containers they run (and are tested)
    in interpret mode."""
    return jax.default_backend() == "cpu"


def route_pallas(override: bool | None = None) -> bool:
    """THE kernel-routing decision (DESIGN.md §11): ``True`` sends a model
    hot path through the Pallas kernels, ``False`` through the pure-jnp
    ref oracles in ``kernels/ref.py``.

    On TPU the Pallas kernels are the production path.  On CPU the default
    is the REF fallback, not interpret-mode Pallas: interpret mode
    simulates the kernel block-by-block in Python-driven XLA ops — orders
    of magnitude slower — which matters because the routed paths are
    traced inside the evaluation backends' bucket ladder (one model
    forward PER LANE, many lanes per tick).  Tests pass ``override=True``
    to force interpret-mode Pallas on CPU and pin ref-vs-Pallas parity
    inside that traced ladder.

    The decision is made at TRACE time (it is ordinary Python), so a
    warmed bucket ladder bakes the route in — rerouting mid-run would be
    a recompile, which the zero-compile contract forbids.
    """
    if override is not None:
        return override
    return jax.default_backend() != "cpu"
