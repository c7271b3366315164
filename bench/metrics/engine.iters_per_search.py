"""Committed iterations per search that ended in the window (reached the
target, or gave up after its iteration budget)."""


def read(run):
    ended = [s for s in run["searches"] if s["ended"]]
    if not ended:
        return None
    return sum(s["iterations"] for s in ended) / len(ended)
