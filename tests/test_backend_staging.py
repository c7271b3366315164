"""The packed staging of a bucket (DESIGN.md §6–§7): each dispatch hands
the jitted ``bucket_eval`` one ``(kp, n + 1)`` float32 host array, the
points in columns ``0..n-1`` and each lane's malicious draw in column
``n`` (NaN honest, the draw for a liar, ``PAD_MARK`` for a pad lane).

The values are held to a reference built straight from ``f_batch`` at
the bucket's width, ``malicious_lie`` and the NaN pad mask, on both
backends that run on one device: honest lanes bit-identical, malicious
lanes corrupted, pad lanes NaN on the device and stripped by
``collect``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grid import malicious_lie
from repro.core.substrates.eval_backend import (PAD_MARK,
                                                InProcessEvalBackend,
                                                bucket_size)
from repro.core.substrates.pod_mesh import PodMeshEvalBackend

BACKENDS = [InProcessEvalBackend, PodMeshEvalBackend]


def _quad_fitness(n=8, seed=3):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, n)).astype(np.float32)
    H = jnp.asarray(A @ A.T + n * np.eye(n, dtype=np.float32))
    x_opt = jnp.asarray(rng.uniform(-0.5, 0.5, n).astype(np.float32))

    @jax.jit
    def f_batch(xs):
        d = xs - x_opt[None, :]
        return 0.5 * jnp.einsum("mi,ij,mj->m", d, H, d)

    return f_batch, n


def _draws(kind, k, rng):
    if kind == "none":
        return None
    if kind == "honest":
        return np.full(k, np.nan)
    u = np.where(rng.random(k) < 0.5, rng.uniform(0.2, 0.8, k), np.nan)
    u[0] = 0.5                  # at least one liar, even at k = 1
    return u


class _Spy:
    """Records what each dispatch hands the jitted bucket program."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, *args, **kwargs):
        self.calls.append((args, kwargs))
        return self.fn(*args, **kwargs)


@pytest.mark.parametrize("backend_cls", BACKENDS)
@pytest.mark.parametrize("width", [8, 128])
@pytest.mark.parametrize("fill", ["one", "all_but_one", "full"])
@pytest.mark.parametrize("mal", ["none", "honest", "mixed"])
def test_packed_bucket_matches_the_direct_reference(backend_cls, width, fill,
                                                    mal):
    f_batch, n = _quad_fitness()
    be = backend_cls(f_batch)
    be.min_bucket = width       # so even one point fills a bucket this wide
    k = {"one": 1, "all_but_one": width - 1, "full": width}[fill]
    rng = np.random.default_rng(width + k)
    pts = rng.uniform(-1, 1, (k, n))
    u = _draws(mal, k, rng)
    be._eval = spy = _Spy(be._eval)
    h = be.submit(pts, u)
    ys = be.collect(h)

    kp = bucket_size(k, be.min_bucket)
    assert kp == width
    assert h.kp == kp and h.k == k and ys.shape == (k,)
    # the staged array: points cast to f32, pad points repeat the last
    # real one, the draw column marks honest, liar and pad lanes
    (staged,), kwargs = spy.calls[0]
    assert kwargs == {} and staged.shape == (kp, n + 1)
    assert staged.dtype == np.float32
    padded = np.concatenate(
        [pts, np.repeat(pts[-1:], kp - k, axis=0)]).astype(np.float32)
    np.testing.assert_array_equal(staged[:, :n], padded)
    draws = np.full(k, np.nan) if u is None else u
    np.testing.assert_array_equal(staged[:k, n], draws.astype(np.float32))
    assert (staged[k:, n] == PAD_MARK).all()

    ref = np.asarray(f_batch(jnp.asarray(padded)))[:k]
    liar = ~np.isnan(draws)
    assert liar.any() == (mal == "mixed")
    np.testing.assert_array_equal(ys[~liar], ref[~liar].astype(np.float64))
    lie = malicious_lie(ref, draws.astype(np.float32))
    np.testing.assert_allclose(ys[liar], lie[liar], rtol=1e-6)
    assert (ys[liar] < ref[liar]).all()          # always an under-report
    pad = np.asarray(h.ys)[k:]
    assert pad.size == kp - k and np.isnan(pad).all()


@pytest.mark.parametrize("backend_cls", BACKENDS)
def test_a_dispatch_hands_bucket_eval_one_host_array(backend_cls):
    """One argument a dispatch, a ring slot's own numpy buffer (aliased,
    not copied, on the CPU), and one array input to the compiled
    program, which keeps the name the trace reader looks for."""
    f_batch, n = _quad_fitness()
    be = backend_cls(f_batch, n_dims=n, max_bucket=16)
    be._eval = spy = _Spy(be._eval)
    be(np.random.default_rng(0).uniform(-1, 1, (11, n)))
    (args, kwargs), = spy.calls
    assert kwargs == {} and len(args) == 1
    assert isinstance(args[0], np.ndarray)
    assert any(args[0] is buf for buf in be._bufs[16])
    jitted = spy.fn
    assert jitted.__name__ == "bucket_eval"
    spec = jax.ShapeDtypeStruct((16, n + 1), np.float32)
    assert len(jax.make_jaxpr(jitted)(spec).jaxpr.invars) == 1
    assert "jit_bucket_eval" in jitted.lower(spec).as_text()
