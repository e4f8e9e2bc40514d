"""device_idle_pct.filter: share of the traced window in which no
operation ran on the device (1 - busy union / window), in %."""
from bench.trace import idle_pct


def read(run):
    return None if run.trace is None else idle_pct(run.trace)
