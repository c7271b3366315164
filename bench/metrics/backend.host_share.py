"""Share of the window the host spent in the evaluation backend's own
code (self time of ``backend.submit`` and ``backend.collect``: staging,
padding, dispatch, slot release), the blocking read-back
(``backend.wait``) left out."""
from bench import span_share


def read(run):
    return span_share.share(run, ("backend.submit", "backend.collect"))
