"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name: the workload entry in
``BENCHMARK.json`` names a configuration (``bench/configs/<file>``) and a
traffic mix (``bench/traffic/<mix>.json``), whose ``driver`` is
``bench/drivers/<driver>.py`` (see ``generator.py``); each metric is read
by ``bench/metrics/<metric>.py``.  A later cell, mix, driver or metric is
new files and new entries, never an edit here.

The run, in order: device check (a TPU, as many chips as the cell asks
for; never a CPU fallback), the compile cache, the stripe and the
program's fitness, warm-up of the cell's bucket ladder and phase-finish
program, the window of ``--seconds`` (traced with ``--trace 1``), the
peak device memory, then the comparison with the plain references
(``check.py``) and the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# -- finding things by name ----------------------------------------------------

def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                   f"{[w['name'] for w in spec['workloads']]}")


def config_of(spec: dict, root: pathlib.Path, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str, bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def _module(bench: pathlib.Path, group: str, name: str):
    """The module in file ``bench/<group>/<name>.py``."""
    if not (bench / group / f"{name}.py").is_file():
        raise KeyError(f"no {bench / group / name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{group}_" + name.replace(".", "_").replace("-", "_"),
        bench / group / f"{name}.py")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: pathlib.Path = BENCH) -> Callable:
    """``read(run) -> value | None`` from ``bench/metrics/<name>.py``."""
    return _module(bench, "metrics", name).read


def driver_class(name: str, bench: pathlib.Path = BENCH) -> type:
    """``Driver`` from ``bench/drivers/<name>.py``."""
    return _module(bench, "drivers", name).Driver


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with a trace its per-layer ones; an entry with ``workloads`` only
    where it lists the cell."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peak_of(kind: str, bench: pathlib.Path = BENCH) -> dict:
    peaks = json.loads((bench / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(peaks)})")
    return peaks[kind]


# -- the system under test, wrapped to be seen -------------------------------

class Recorder:
    """The evaluation backend as the window's drivers see it: every call
    goes to the program's backend; while ``on``, each bucket's points,
    malicious draws and returned values are kept for the check, and the
    lanes and dispatches counted."""

    def __init__(self, backend):
        self.backend = backend
        self.min_bucket = backend.min_bucket
        self.on = False
        self.ks: List[int] = []          # lanes of each dispatch
        self.lanes = 0                   # lanes whose values came back
        self.blocks: List[tuple] = []    # (points, mal_u, values)
        self._pending: Dict[int, tuple] = {}

    def warm(self, n_dims: int, max_k: int):
        self.backend.warm(n_dims, max_k)
        return self

    def submit(self, pts, mal_u=None, lane_tags=None):
        handle = self.backend.submit(pts, mal_u, lane_tags=lane_tags)
        if self.on:
            k = len(pts)
            self.ks.append(k)
            self._pending[handle.seq] = (
                np.array(pts, np.float64),
                np.full(k, np.nan) if mal_u is None
                else np.array(mal_u, np.float64))
        return handle

    def collect(self, handle):
        ys = self.backend.collect(handle)
        rec = self._pending.pop(handle.seq, None)
        if rec is not None:
            self.lanes += len(ys)
            self.blocks.append((rec[0], rec[1], np.array(ys, np.float64)))
        return ys

    def __call__(self, pts, mal_u=None):
        return self.collect(self.submit(pts, mal_u))


class FinishRecorder:
    """Stands in for the engine's jitted phase-finish: calls it, and while
    ``on`` keeps each call's samples and returned direction."""

    def __init__(self, fn):
        self.fn = fn
        self.on = False
        self.calls: List[tuple] = []

    def __call__(self, deltas, ys, *args, **kw):
        out = self.fn(deltas, ys, *args, **kw)
        if self.on:
            self.calls.append((deltas, ys, out[0]))
        return out


class CompileCounter:
    """Counts traces and compiles JAX reports while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.count += 1


# -- the problem -----------------------------------------------------------------

@dataclasses.dataclass
class Problem:
    config: dict
    stars: np.ndarray
    quad: np.ndarray
    truth: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    step: np.ndarray
    target: float
    backend: Recorder


def build_problem(config: dict) -> Problem:
    """The configuration's stripe (made here, from its seed), the
    program's fitness over it, and the search target: the float64
    reference NLL at the stripe's truth plus the stated tolerance."""
    from bench import data, reference
    from repro.core.substrates.eval_backend import InProcessEvalBackend
    from repro.data import sdss

    st = config["stripe"]
    stars, quad, truth = data.make_stripe(
        st["n_stars"], st["n_quad"], st["seed"], st["wedge_lo"],
        st["wedge_hi"])
    f_batch, _ = sdss.make_fitness(
        sdss.Stripe(name=config["name"], stars=stars, quad=quad,
                    truth=truth))
    box = config["box"]
    lo = np.asarray(box["lo"], np.float64)
    hi = np.asarray(box["hi"], np.float64)
    f_truth = float(reference.nll(truth[None].astype(np.float64), stars,
                                  quad, st["wedge_lo"], st["wedge_hi"])[0])
    return Problem(config=config, stars=stars, quad=quad, truth=truth,
                   lo=lo, hi=hi,
                   step=np.float32(box["step_frac"]) * (hi - lo).astype(
                       np.float32),
                   target=f_truth + config["target"]["tolerance"],
                   backend=Recorder(InProcessEvalBackend(f_batch)))


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# -- one run ---------------------------------------------------------------------

class Session:
    """The program set up for one configuration: the problem, with the
    recorders in the path.  ``window`` may run many times (the control
    script reads a dozen seeds in one process); ``close`` restores the
    engine's phase-finish."""

    def __init__(self, cfg: dict):
        from repro.core import engine as engine_mod

        self.cfg = cfg
        self.problem = build_problem(cfg)
        self.compiles = CompileCounter()
        self._engine = engine_mod
        self.finish = FinishRecorder(engine_mod._regression_direction)
        engine_mod._regression_direction = self.finish

    def close(self) -> None:
        self._engine._regression_direction = self.finish.fn

    def window(self, mix: dict, seed: int, seconds: float,
               trace_dir: Optional[pathlib.Path] = None,
               bench: pathlib.Path = BENCH) -> dict:
        """Warm the cell's programs, then drive ``mix`` for ``seconds``;
        returns what the window recorded, and ``t0``, its start."""
        import jax

        rec, fin, cc = self.problem.backend, self.finish, self.compiles
        rec.ks, rec.lanes, rec.blocks = [], 0, []
        fin.calls, cc.count = [], 0
        driver = driver_class(mix["driver"], bench)(self.problem, mix, seed,
                                                    rec)
        t_warm = time.perf_counter()
        driver.warm()
        _warm_finish(fin, self.cfg)
        warm_s = time.perf_counter() - t_warm
        if trace_dir is not None:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=_profile_options())
        rec.on = fin.on = cc.on = True
        t0 = time.perf_counter()
        try:
            extra = driver.run(t0 + seconds)
        finally:
            rec.on = fin.on = cc.on = False
            window_s = time.perf_counter() - t0
            if trace_dir is not None:
                jax.profiler.stop_trace()
        return {"t0": t0, "window_s": window_s, "warm_s": warm_s,
                "driver": driver,
                "extra": extra, "blocks": rec.blocks, "ks": rec.ks,
                "lanes": rec.lanes, "finishes": fin.calls,
                "compiles": cc.count}

    def readings(self, w: dict, seed: int, **control) -> dict:
        from bench import check
        return check.readings(self.problem, w["blocks"], w["finishes"],
                              w["driver"].searches, seed, **control)


def run_cell(root: pathlib.Path, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True,
             config: Optional[dict] = None, bench: pathlib.Path = BENCH,
             log=print) -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    ``config`` replaces the cell's configuration file (tests shrink it);
    ``require_tpu=False`` skips only the look for a chip; ``bench`` is
    where traffic mixes, drivers and metric readers are found."""
    from bench import check

    spec = load_benchmark(root)
    wl = workload(spec, name)
    t_jax = time.perf_counter()
    import jax
    t_devices = time.perf_counter()
    info = device_info()
    t_cache = time.perf_counter()
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if require_tpu and (info["platform"] != "tpu"
                        or info["count"] < wl["chips"]):
        raise NoDevice(f"cell {name} needs {wl['chips']} TPU chip(s); JAX "
                       f"found {info['count']} {info['platform']} device(s)")
    peak = peak_of(info["kind"]) if trace else None
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache

        log(f"[setup] compile cache: {enable_compile_cache()}")
        # every program this cell runs is cached, however fast it compiled
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg = config if config is not None else config_of(spec, root,
                                                      wl["config"])
    t_build = time.perf_counter()
    session = Session(cfg)
    build_s = time.perf_counter() - t_build
    trace_dir = root / "bench_out" / "trace" if trace else None
    try:
        w = session.window(traffic_of(wl["traffic"], bench), seed, seconds,
                           trace_dir, bench)
    finally:
        session.close()
    setup_s = w["t0"] - t_start
    log(f"[setup] {setup_s:.3f}s: {t_jax - t_start:.3f}s interpreter and "
        f"harness, {t_devices - t_jax:.3f}s import jax, "
        f"{t_cache - t_devices:.3f}s devices (runtime start), "
        f"{t_build - t_cache:.3f}s compile cache, {build_s:.3f}s stripe and "
        f"fitness, {w['warm_s']:.3f}s warm-up")
    window_s = w["window_s"]
    searches = w["driver"].searches
    mem = memory_peak()
    log(f"[window] {window_s:.3f}s: {w['lanes']} evaluations in "
        f"{len(w['ks'])} dispatches, {len(searches)} searches started, "
        f"compiles {w['compiles']}")

    reduced = None
    if trace:
        from bench import trace_reduce
        tr = trace_reduce.load(trace_dir)
        reduced = {"busy_s": trace_reduce.busy_seconds(tr),
                   "programs": trace_reduce.program_times(tr),
                   "top_ops": trace_reduce.top_ops(tr),
                   "idle_gaps": trace_reduce.idle_gaps(tr)}

    checks = check.compare(session.problem, w["blocks"], w["finishes"],
                           searches, w["compiles"], seed)
    run = {"setup_s": setup_s, "window_s": window_s, "lanes": w["lanes"],
           "ks": list(w["ks"]),
           "searches": [{"hit": s.hit, "ended": s.ended,
                         "iterations": s.engine.iteration}
                        for s in searches],
           "server": w["extra"].get("server"), "trace": reduced,
           "peak": peak, "config": cfg}
    metrics = {}
    for m in cell_metrics(spec, name, trace):
        v = metric_reader(m["name"], bench)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    ended = [s for s in searches if s.ended]
    device = dict(info, memory_peak_bytes=mem)
    # attempted: searches started in the window; failed: those that gave
    # up after their iteration budget without reaching the target
    line = {"correct": all(c["ok"] for c in checks.values()),
            "attempted": len(searches),
            "failed": sum(1 for s in ended if not s.hit),
            "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = window_s
        line["breakdown"] = {"device_ops": reduced["top_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def _warm_finish(finish: FinishRecorder, cfg: dict) -> None:
    """Compile and run the phase-finish program at the shape the engine
    calls it with: ``m_regression`` samples of ``n_params`` offsets."""
    import jax.numpy as jnp

    from repro.core.engine import AnmConfig

    anm = cfg["anm"]
    d = AnmConfig()
    m, n = anm["m_regression"], cfg["n_params"]
    rng = np.random.default_rng(0)
    # float64 host arrays converted as the engine converts them, so the
    # conversions' own small programs are warm too
    deltas, ys = rng.uniform(-1, 1, (m, n)), rng.normal(size=m)
    z = np.zeros(n)
    out = finish(*(jnp.asarray(a, jnp.float32)
                   for a in (deltas, ys, z, z - 1, z + 1)),
                 outlier_guard=d.outlier_guard, ridge=d.ridge,
                 damping=anm["damping"], a_min=anm["alpha_min"],
                 a_max=anm["alpha_max"])
    out[0].block_until_ready()


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts
