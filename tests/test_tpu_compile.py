"""The chip path compiles for a TPU v5e chip, with no chip attached.

The TPU compiler is installed beside the CPU backend, and it compiles for
a described ``v5e:2x2`` topology.  That refuses what interpret mode cannot
see: block shapes that do not tile, loads Mosaic cannot lower, programs
larger than the chip's 16 GB.  Each test compiles one kernel or one main-
path program for the first described chip, passing ``interpret=False``
explicitly (``jax.default_backend()`` is still the CPU here), and checks
that the Pallas kernel is in the program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a test module that
decided at import whether its tests exist would give parallel workers
different collections.  Keep every such compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compat
from repro.kernels import flash_attention as fa_kernel
from repro.kernels import gram as gram_kernel
from repro.kernels import wkv6 as wkv6_kernel

HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip_routes(monkeypatch):
    """Steer the trace-time platform decisions to what they are on the
    chip: kernels compiled, not interpreted, and model hot paths routed
    through them.  The decisions are made at trace time, so JAX's trace
    caches are cleared on the way in (a CPU trace of the same function
    must not be reused) and on the way out (nor may later CPU tests reuse
    the chip's)."""
    monkeypatch.setattr(compat, "interpret_default", lambda: False)
    monkeypatch.setattr(compat, "route_pallas",
                        lambda override=None:
                        True if override is None else override)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_gram_compiles(one_chip):
    c = gram_kernel.gram.lower(_shape(one_chip, (1024, 128)),
                               _shape(one_chip, (1024,)),
                               block_m=512, interpret=False).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [(1, 4, 32, 16), (1, 64, 256, 64)],
                         ids=["smoke", "rwkv6_7b"])
def test_wkv6_compiles(one_chip, shape, dtype):
    # (1, 64, 256, 64) is the published rwkv6-7b head layout; bf16 is the
    # model's activation dtype
    b, h, t, k = shape
    c = jax.jit(lambda r, k_, v, lw, u: wkv6_kernel.wkv6(
        r, k_, v, lw, u, chunk=min(256, t), interpret=False)).lower(
        *[_shape(one_chip, shape, dtype)] * 4,
        _shape(one_chip, (h, k), dtype)).compile()
    assert _has_kernel(c)


def test_flash_attention_compiles(one_chip):
    qkv = [_shape(one_chip, (4, 256, 128))] * 3
    c = jax.jit(lambda q, k, v: fa_kernel.flash_attention(
        q, k, v, causal=True, interpret=False)).lower(*qkv).compile()
    assert _has_kernel(c)


def test_paper_fitness_bucket_fits_one_chip(one_chip):
    """The top bucket of the paper deployment's ladder (2048 hosts) over
    stripe79's shapes: 100k stars, 4096 quadrature points."""
    from repro.data import sdss

    def f_batch(ps, stars, quad):
        return jax.vmap(lambda p: sdss.log_likelihood(p, stars, quad))(ps)

    c = jax.jit(f_batch).lower(_shape(one_chip, (2048, 8)),
                               _shape(one_chip, (100_000, 3)),
                               _shape(one_chip, (4096, 3))).compile()
    mem = c.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_phase_finish_uses_gram_kernel_at_full_precision(one_chip,
                                                        on_chip_routes):
    """The engine's phase-finish at the paper's m=1000: the XᵀX goes
    through the gram kernel, and no dot is left at the TPU's default
    one-pass bf16 precision."""
    from repro.core.engine import _regression_direction

    vec = _shape(one_chip, (8,))
    c = _regression_direction.lower(
        _shape(one_chip, (1000, 8)), _shape(one_chip, (1000,)), vec, vec,
        vec, outlier_guard=True, ridge=1e-8, damping=1e-6, a_min=0.0,
        a_max=2.0).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    dots = [ln for ln in text.splitlines()
            if re.search(r"= \S+ (dot|convolution)\(", ln)]
    assert dots
    low = [ln for ln in dots
           if "operand_precision={highest,highest}" not in ln]
    assert not low, low


def test_lm_bucket_uses_wkv6_kernel(one_chip, on_chip_routes):
    """The LM objective's bucket program (rwkv6-7b smoke width, bf16
    activations) with the model's routed wkv6 compiled as the kernel."""
    from repro.core.substrates.lm_loss import (LmLossEvalBackend,
                                               make_lm_workload)

    be = LmLossEvalBackend(make_lm_workload("rwkv6-7b", k=8))
    c = be._eval.lower(_shape(one_chip, (16, 9))).compile()
    assert _has_kernel(c)
