"""Paper-figure entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``python -m benchmarks.run``.
The figures compare iteration counts and fitness with the paper; speed is
measured on the chip by ``bench/`` alone.
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = [
    ("fig2_convergence", "paper Fig. 2 — ANM convergence on two stripes"),
    ("fig3_linesearch", "paper Fig. 3 — randomized line search escapes"),
    ("anm_vs_baselines", "paper §VI — ANM vs CGD vs numerical Newton"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single benchmark")
    args = ap.parse_args()
    failures = 0
    for name, desc in MODULES:
        if args.only and args.only != name:
            continue
        print(f"# === {name}: {desc} ===", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
        except Exception:
            failures += 1
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == '__main__':
    main()
