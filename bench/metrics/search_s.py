"""Time to a solution of stated accuracy: the window's seconds over the
searches whose committed best fitness reached the target in it."""


def read(run):
    hits = sum(1 for s in run["searches"] if s["hit"])
    return run["window_s"] / hits if hits else None
