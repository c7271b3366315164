"""Fitness results the device returned to the searches, per second of the
whole window."""


def read(run):
    return run["lanes"] / run["window_s"] if run["lanes"] else None
