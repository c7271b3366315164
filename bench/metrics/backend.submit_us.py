"""Mean self time of one of the backend's submits (``backend.submit``:
staging the bucket, its dispatch and the start of its read-back), in
microseconds.  Reads None with no submit, and for a program without
spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    submit = spans.totals().get("backend.submit", {})
    if not submit.get("count", 0):
        return None
    return submit["self_ns"] / submit["count"] / 1000.0
