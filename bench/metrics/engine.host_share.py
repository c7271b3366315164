"""Share of the window the host spent in the ANM engine (self time of the
``engine.*`` spans: generate, assimilate, and the phase-finish call with
its conversions and read-back)."""
from bench import span_share


def read(run):
    return span_share.share(run, ("engine.",))
