"""Share of the window the host spent in the work server's intake (self
time of the ``intake.*`` spans: the loopback codec, ``WorkServer.handle``,
the registry's sweep)."""
from bench import span_share


def read(run):
    return span_share.share(run, ("intake.",))
