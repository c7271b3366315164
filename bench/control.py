#!/usr/bin/env python3
"""The readings the limits in ``check.py`` are set from, on the chip.

    python3 bench/control.py --workload stripe79.batched --seeds 12 --control 3 --seconds 10

In one process: for each of ``--seeds`` seeds, a window of the cell at its
own size, and the program's compared numbers (the lower readings); for
the first ``--control`` of them, the same numbers with the control in the
program's place (the upper readings): the references computed in
bfloat16, one precision below the configuration's float32, on the same
lanes, centers and phase-finish samples.  The benchmark's own runs never
run this.  Prints one JSON line per seed and a summary last.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_readings(session, w: dict, seed: int) -> dict:
    from bench import reference
    return session.readings(w, seed, nll=reference.nll_lowp,
                            direction=reference.direction_lowp)


def collect(session, mix: dict, seeds, seconds: float, n_control: int,
            log=print) -> dict:
    """Program readings for every seed, control readings for the first
    ``n_control``; returns ``{"program": [...], "control": [...]}``."""
    out = {"program": [], "control": []}
    for i, seed in enumerate(seeds):
        w = session.window(mix, seed, seconds)
        row = dict(session.readings(w, seed), seed=seed,
                   compiles=w["compiles"], lanes=w["lanes"],
                   finishes=len(w["finishes"]),
                   searches=len(w["driver"].searches))
        out["program"].append(row)
        log(json.dumps({"program": row}))
        if i < n_control:
            crow = dict(control_readings(session, w, seed), seed=seed)
            out["control"].append(crow)
            log(json.dumps({"control": crow}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    info = harness.device_info()
    if info["platform"] != "tpu":
        print(f"control: no TPU ({info})", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = harness.load_benchmark(ROOT)
    wl = harness.workload(spec, args.workload)
    session = harness.Session(harness.config_of(spec, ROOT, wl["config"]))
    try:
        res = collect(session, harness.traffic_of(wl["traffic"]),
                      range(args.first_seed, args.first_seed + args.seeds),
                      args.seconds, args.control)
    finally:
        session.close()
    keys = [k for k in res["program"][0] if k.endswith(("err", "gap"))]
    print(json.dumps({"workload": args.workload, "device": info,
                      "lower": {k: max(r[k] for r in res["program"])
                                for k in keys},
                      "upper": {k: min(r[k] for r in res["control"])
                                for k in keys} if res["control"] else None,
                      "seconds": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
