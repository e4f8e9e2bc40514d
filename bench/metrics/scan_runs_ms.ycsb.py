"""scan_runs_ms.ycsb: every scan's slices of its touched runs, the
key-by-key merge under the memtable and the final sort
(``bloomrf/store/scan/runs``, ``Store._scan_one``), ms per step, from the
program's spans."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "store/scan/runs")
