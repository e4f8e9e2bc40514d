"""store_host_ms.ycsb: per step, its wall time less the device's busy
time inside it (ms/step): the store's host path (memtable walk, run
slice, row merge, flush and compaction) and the host's dispatch, from
the profiler trace and the benchmark's step spans."""
from bench.stats import host_ms_per_step


def read(run):
    return host_ms_per_step(run)
