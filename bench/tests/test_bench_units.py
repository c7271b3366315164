"""The yardstick's parts: work counts, peaks, references, trace reduction."""
import json
import pathlib

import numpy as np
import pytest

from bench import check, generator, harness, reference, trace_reduce, work

FIXTURE = pathlib.Path(__file__).with_name("trace_fixture.json")


def test_flops_by_hand():
    # one star: halo 8 + tube 18 + mixture 8; one quadrature point: 28;
    # once per lane: 21
    assert work.flops_per_eval(1, 0) == 34 + 21
    assert work.flops_per_eval(0, 1) == 28 + 21
    assert work.flops_per_eval(100_000, 4096) == 3_514_709
    # stars and quadrature points read once, 8 parameters + 1 draw in and
    # 1 value out per lane, float32
    assert work.bucket_bytes(2, 10, 5, 8) == 4 * (30 + 15 + 18 + 2)


def test_least_time_takes_the_larger_bound():
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e10}
    one = work.least_seconds(1, 1000, 0, 8, peak)
    assert one == pytest.approx(work.bucket_bytes(1, 1000, 0, 8) / 1e10)
    many = work.least_seconds(10_000, 1000, 0, 8, peak)
    assert many == pytest.approx(10_000 * work.flops_per_eval(1000, 0) / 1e12)


def test_the_seed_orders_the_listed_members_only():
    mix = {"members": [0, 1, 2, 4, 7]}
    orders = []
    for seed in (2**31 + 17, 5):
        it = generator.members(mix, seed)
        cycles = [[next(it) for _ in mix["members"]] for _ in range(3)]
        assert all(sorted(c) == mix["members"] for c in cycles)
        orders.append(cycles[0])
    assert orders[0] != orders[1]
    it = generator.members(mix, 5)
    assert [next(it) for _ in mix["members"]] == orders[1]


def test_peaks_keyed_by_device_kind():
    v5e = harness.peak_of("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        harness.peak_of("TPU v9 imaginary")


def test_reference_agrees_with_the_program_likelihood():
    from bench import data
    from repro.data import sdss
    stars, quad, truth = data.make_stripe(500, 64, 3, sdss.WEDGE_LO,
                                          sdss.WEDGE_HI)
    pts = np.random.default_rng(0).uniform(sdss.LO, sdss.HI, (16, 8))
    f_batch, _ = sdss.make_fitness(sdss.Stripe("t", stars, quad, truth))
    got = np.asarray(f_batch(pts.astype(np.float32)), np.float64)
    ref = reference.nll(check.staged(pts), stars, quad, sdss.WEDGE_LO,
                        sdss.WEDGE_HI)
    assert np.max(np.abs(got - ref) / np.abs(ref)) < 1e-5


def test_reference_direction_agrees_with_the_phase_finish():
    import jax.numpy as jnp

    from repro.core import engine
    rng = np.random.default_rng(1)
    m, n = 200, 8
    a = rng.normal(size=(n, n))
    h = a @ a.T / n + np.eye(n)
    g = rng.normal(size=n)
    deltas = rng.uniform(-0.5, 0.5, (m, n))
    ys = 3.0 + deltas @ g + 0.5 * np.einsum("mi,ij,mj->m", deltas, h, deltas)
    ys = ys + rng.normal(0, 1e-4, m)
    z = np.zeros(n, np.float32)
    d, _, _ = engine._regression_direction(
        jnp.asarray(deltas, jnp.float32), jnp.asarray(ys, jnp.float32),
        z, z - 4, z + 4, outlier_guard=True, ridge=1e-8, damping=1e-6,
        a_min=0.0, a_max=2.0)
    ref = reference.direction(deltas.astype(np.float32),
                              ys.astype(np.float32), 1e-6)
    assert check.cos_gap(np.asarray(d, np.float64), ref) < 1e-6
    assert check.cos_gap(np.zeros(n), ref) == 1.0


def test_lie_matches_the_fleet_model():
    from repro.core.grid import malicious_lie
    y = np.array([-2.0, 0.0, 5.13])
    u = np.array([0.2, 0.5, 0.8])
    assert np.array_equal(reference.lie(y, u), malicious_lie(y, u))


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]


def test_trace_reduction_on_a_recorded_chip_trace():
    tr = json.loads(FIXTURE.read_text())
    dev = trace_reduce.device_planes(tr)
    assert dev
    ops = [e for line in dev[0]["lines"] if line["name"] == "XLA Ops"
           for e in line["events"]]
    # busy time is the union of the op intervals: between the longest op
    # and the plain sum of all of them
    busy = trace_reduce.busy_seconds(tr)
    assert max(d for _, _, d in ops) * 1e-9 <= busy <= \
        sum(d for _, _, d in ops) * 1e-9
    # each program by its jit name: its device time and executions
    progs = trace_reduce.program_times(tr)
    t, n = progs["jit_bucket_eval"]
    assert n >= 1 and t > 0
    assert "jit__regression_direction" in progs
    assert trace_reduce.module_name("jit_bucket_eval(12345)") == \
        "jit_bucket_eval"
    assert len(trace_reduce.top_ops(tr)) <= 10
    gaps = trace_reduce.idle_gaps(tr)
    assert gaps and all(g[1] > 0 for g in gaps)


def test_planes_that_are_not_chips_do_not_count():
    # a recorded TPU trace also holds an empty "/device:CUSTOM:Megascale
    # Trace" plane; the busy time averages over chips only
    tr = json.loads(FIXTURE.read_text())
    busy = trace_reduce.busy_seconds(tr)
    tr["planes"].append({"name": "/device:CUSTOM:Megascale Trace",
                         "lines": []})
    assert [p["name"] for p in trace_reduce.device_planes(tr)] == \
        ["/device:TPU:0"]
    assert trace_reduce.busy_seconds(tr) == busy
