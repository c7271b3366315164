"""Share of the window the host spent blocked on the device in the
backend's collect (``backend.wait``: the bucket's read-back, which waits
for the evaluation and copies its values to the host)."""
from bench import span_share


def read(run):
    return span_share.share(run, ("backend.wait",), "total_ns")
