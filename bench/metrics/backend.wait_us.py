"""Mean time the host spent blocked in one of the backend's collects
(``backend.wait``: what is left of the bucket's read-back when it is
collected), in microseconds.  Reads None with no collect, and for a
program without spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    wait = spans.totals().get("backend.wait", {})
    if not wait.get("count", 0):
        return None
    return wait["total_ns"] / wait["count"] / 1000.0
