"""ycsb_p95_ms: 95th percentile over all operations of the window; an
operation's latency is the wall time of the step that carried it."""
from bench.stats import op_percentile_ms


def read(run):
    return op_percentile_ms(run, 95)
