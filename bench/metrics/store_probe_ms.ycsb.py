"""store_probe_ms.ycsb: device time of the store's scan-pruning program
(``core/engine.py``'s ``StackedProbe``: the bloomRF probe of every run in
one fused gather) per step, from the profiler trace."""
from bench.stats import per_step_ms
from bench.trace import PROGRAM, op_seconds


def read(run):
    if run.trace is None:
        return None
    secs = op_seconds(run.trace, PROGRAM["store_scan"])
    return per_step_ms(run, secs) if secs > 0 else None
