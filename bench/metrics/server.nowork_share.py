"""Share of the work server's messages answered with a no-work reply
(``ServerCounters``)."""


def read(run):
    c = run.get("server")
    if not c or not c.get("messages"):
        return None
    return 100.0 * c["nowork_replies"] / c["messages"]
