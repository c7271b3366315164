"""The whole window's share of the chip's peak: operations of the
evaluations returned (``bench/work.py``), per second of the window, over
the peak rate."""
from bench import work


def read(run):
    if not run["lanes"] or not run.get("peak"):
        return None
    st = run["config"]["stripe"]
    flops = run["lanes"] * work.flops_per_eval(st["n_stars"], st["n_quad"])
    return 100.0 * flops / run["window_s"] / run["peak"]["flops_per_s"]
