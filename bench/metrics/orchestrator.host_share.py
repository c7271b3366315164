"""Share of the window the host spent in the multi-search orchestrator
itself (self time of the ``orchestrator.*`` spans: the scheduling round's
loop, the coalescer's dispatch of a round, forced or not, and its lane
collects), the fleet's ticks and the backend's calls nested in them left
out.  A program without these spans reads None."""
from bench import span_share


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    if not any(name.startswith("orchestrator.") for name in spans.totals()):
        return None
    return span_share.share(run, ("orchestrator.",))
