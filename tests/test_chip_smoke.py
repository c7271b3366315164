"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script's ``main`` runs only on a TPU; its phases are plain functions,
so the checks they make (the float64 references, zero compiles after
warm-up, committed improvement, the served search) are exercised here on
every run, and the four-chip phases run on four virtual CPU devices in a
child process.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as S  # noqa: E402


@pytest.fixture(scope="module")
def problem():
    return S.paper_problem(n_stars=1500, n_quad=256, n_hosts=128, m=64)


def test_reference_nll_agrees_with_the_model(problem):
    from repro.data import sdss

    stripe, f_batch, _ = problem
    pts = np.random.default_rng(5).uniform(sdss.LO, sdss.HI, (40, 8))
    pts = np.vstack([pts, stripe.truth]).astype(np.float32)
    ref = S.reference_nll(pts, stripe.stars, stripe.quad, sdss.WEDGE_LO,
                          sdss.WEDGE_HI, chunk=7)
    assert S.rel_err(f_batch(pts), ref) < 1e-5


def test_reference_direction_recovers_a_quadratic():
    rng = np.random.default_rng(2)
    n = 4
    a = rng.normal(size=(n, n))
    h = a @ a.T + n * np.eye(n)
    g = rng.normal(size=n)
    d = rng.uniform(-1, 1, (200, n))
    y = 3.0 + d @ g + 0.5 * np.einsum("mi,ij,mj->m", d, h, d)
    np.testing.assert_allclose(S.reference_direction(d, y, 1e-6),
                               -np.linalg.solve(h + 1e-6 * np.eye(n), g),
                               rtol=1e-8)


def test_phase_fitness(problem):
    stripe, f_batch, _ = problem
    assert S.phase_fitness(stripe, f_batch, n_points=32)["max_rel_err"] \
        <= S.FITNESS_RTOL


def test_phase_direction(problem):
    _, f_batch, spec = problem
    assert S.phase_direction(f_batch, spec, m=200,
                             require_kernel=False)["cosine"] \
        >= S.DIRECTION_COS


def test_phase_batched_then_served(problem):
    from repro.core.substrates.eval_backend import InProcessEvalBackend

    _, f_batch, spec = problem
    backend = InProcessEvalBackend(f_batch)
    out = S.phase_batched(backend, spec)
    assert out["engine"].iteration == spec.anm.max_iterations
    assert out["evals"] > 0 and out["dispatches"] > 0
    served = S.phase_served(backend, spec)
    assert served["messages"] > served["leases"] > 0


def test_phase_lm():
    assert S.phase_lm(n_points=8, require_kernel=False)["max_rel_err"] \
        <= S.LM_RTOL


def test_failed_check_raises():
    with pytest.raises(S.SmokeFailure):
        S.check(False, "boom")


def test_main_refuses_a_cpu(capsys):
    assert S.main([]) == 2
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_script_alone_fails(tmp_path):
    """Without the repo beside it the script must not report success."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


_FOUR_DEVICES = """
import sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke as S
assert len(jax.devices()) == 4, jax.devices()
_, f_batch, spec = S.paper_problem(n_stars=800, n_quad=256, n_hosts=128,
                                   m=64)
pod = S.phase_pod_mesh(f_batch, spec, n_points=48)
lm = S.phase_lm_mesh(n_points=8)
print("RESULT", pod["bit_identical"], lm["bit_identical"])
"""


def test_cross_chip_phases_on_four_cpu_devices():
    """``--chips 4``'s phases on four virtual CPU devices: the pod mesh
    takes every device, and the (data=2, model=2) LM mesh scores each
    lane on the whole token batch."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c",
                        _FOUR_DEVICES.format(root=str(ROOT))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "RESULT True True" in r.stdout, r.stdout[-3000:]


def test_last_line_is_the_verdict(monkeypatch, capsys):
    """On a TPU the last stdout line is the driver's JSON verdict; here
    every phase is stubbed and the device reported as a chip."""
    from types import SimpleNamespace as NS

    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(S, "device_info", lambda: info)
    spec = NS(grid=NS(n_hosts=0), anm=NS(m_regression=0))
    monkeypatch.setattr(S, "paper_problem",
                        lambda: (NS(stars=[], quad=[]), None, spec))
    for name in ("phase_fitness", "phase_direction", "phase_batched",
                 "phase_served", "phase_lm"):
        monkeypatch.setattr(S, name, lambda *a, **k: {})
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "(stubbed)")
    assert S.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": info}
