"""Share of the window the host spent in the simulated volunteer fleet
itself (self time of the ``fleet.*`` spans: the batched grid's tick
physics and issuance, the client pool's event heap)."""
from bench import span_share


def read(run):
    return span_share.share(run, ("fleet.",))
