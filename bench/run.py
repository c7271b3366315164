#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it finds.

    python3 bench/run.py --workload stripe79.batched --seed 7 --seconds 20 --trace 0

Prints progress on stderr and, as the last line of stdout, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with a trace ``breakdown``, and last ``checks``, each compared
number beside its limit.  Exits 3 without a result when JAX finds no TPU
or fewer chips than the cell needs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), T_START, log=log)
    except harness.NoDevice as e:
        log(f"bench: {e}")
        return 3
    for k, c in line["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        log(f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if ok else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
