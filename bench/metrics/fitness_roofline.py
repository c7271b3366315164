"""The fitness buckets' share of their roofline: the least time the chip
could take for the window's buckets (``bench/work.py`` operations over
the peak rate, or least bytes over the peak bandwidth, whichever is
larger, bucket by bucket) over the device time of the bucket programs in
the trace."""
from bench import work


def read(run):
    tr = run.get("trace")
    prog = tr and tr["programs"].get("jit_bucket_eval")
    if not prog or not prog[0] or not run["ks"] or not run.get("peak"):
        return None
    st = run["config"]["stripe"]
    least = sum(work.least_seconds(k, st["n_stars"], st["n_quad"],
                                   run["config"]["n_params"], run["peak"])
                for k in run["ks"])
    return 100.0 * least / prog[0]
