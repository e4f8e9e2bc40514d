"""wal_append_ms.ycsb: framing and writing each acknowledged write to
the write-ahead log (``bloomrf/wal/append``, ``store/wal.py``), ms per
step, from the program's spans."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "wal/append")
