"""Share of the window the host spent in the work server's scans of the
whole fleet: self time of ``intake.sweep`` (the registry's churn sweep
over every host) and ``intake.lapse`` (the scan of every outstanding
lease each time the earliest deadline passes).  Reads None for a program
without spans; a program without the ``intake.lapse`` span reads its
sweep alone."""
from bench import span_share


def read(run):
    return span_share.share(run, ("intake.sweep", "intake.lapse"))
