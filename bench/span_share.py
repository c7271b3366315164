"""Shares of the measured window that the program's own spans
(``repro.obs.spans``) spent in one layer, for the per-layer host-time
metrics.  The program records span totals only while the profiler
traces, which the harness does for exactly the window of a ``--trace 1``
run.  A program without spans, or a run that recorded none, reads None."""


def share(run, names, field="self_ns"):
    """100 × the summed ``field`` of the spans whose name starts with one
    of ``names``, over the window's nanoseconds."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    totals = spans.totals()
    if not totals:
        return None
    ns = sum(t[field] for name, t in totals.items()
             if name.startswith(tuple(names)))
    return 100.0 * ns * 1e-9 / run["window_s"]
