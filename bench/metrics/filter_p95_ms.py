"""filter_p95_ms: 95th percentile over all queries of the window; a
query's latency is its batch's wall time until the verdicts are on the
host."""
from bench.stats import op_percentile_ms


def read(run):
    return op_percentile_ms(run, 95)
