"""Device time of one phase-finish program (``_regression_direction``),
from the trace."""


def read(run):
    tr = run.get("trace")
    prog = tr and tr["programs"].get("jit__regression_direction")
    if not prog or not prog[1]:
        return None
    return 1e3 * prog[0] / prog[1]
