"""Evaluations per backend dispatch in the window."""


def read(run):
    return sum(run["ks"]) / len(run["ks"]) if run["ks"] else None
