"""Where JAX's persistent compilation cache lives, decided in one place.

A cold start on the chip compiles every bucket shape of the fitness ladder,
so the entry points that reach the chip (``chip_smoke.py`` and
``python -m repro.server.sim``) keep compiled programs across runs.  The
cache key includes the directory, so the directory must not move between
runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names, which JAX
reads itself, or the fixed ``.jax_cache`` directory at the repo root
(listed in ``.gitignore``).  Tests never call this: they compile for the
CPU and keep no cache.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is already set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
