"""probe_device_ms.filter: device time of the filter's range-probe
program per batch (the probe engine, ``core/engine.py``: hashing, word
gather, bit tests), from the profiler trace."""
from bench.stats import per_step_ms
from bench.trace import PROGRAM, op_seconds


def read(run):
    if run.trace is None:
        return None
    secs = op_seconds(run.trace, PROGRAM["filter_probe"])
    return per_step_ms(run, secs) if secs > 0 else None
