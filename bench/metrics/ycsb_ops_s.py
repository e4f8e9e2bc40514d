"""ycsb_ops_s: YCSB operations completed per second of the window."""
from bench.stats import ops_per_s


def read(run):
    return ops_per_s(run)
