"""Share of the coalescer's dispatches that a collect on the still-open
round forced (``orchestrator.forced``) among all its dispatches, those of
whole rounds (``orchestrator.flush``) included: the rounds that a search's
mid-round phase decision split in two.  Reads None with no dispatch, and
for a program without these spans."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:
        return None
    totals = spans.totals()
    forced = totals.get("orchestrator.forced", {}).get("count", 0)
    whole = totals.get("orchestrator.flush", {}).get("count", 0)
    if not forced + whole:
        return None
    return 100.0 * forced / (forced + whole)
