"""scan_memtable_ms.ycsb: the memtable walk of every scan
(``bloomrf/store/scan/memtable``, ``Store._scan_one``), ms per step, from
the program's spans."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "store/scan/memtable")
