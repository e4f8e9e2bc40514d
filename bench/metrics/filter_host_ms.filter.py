"""filter_host_ms.filter: per batch, its wall time less the device's busy
time inside it (ms/batch): the facade's encode, the bounds' transfer,
dispatch and the wait for the verdicts on the host, from the profiler
trace and the benchmark's step spans."""
from bench.stats import host_ms_per_step


def read(run):
    return host_ms_per_step(run)
