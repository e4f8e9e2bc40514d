"""probe_roofline.filter: the filter's probe program's share of its
roofline, in %.

The least time is the bytes the bloomRF probe algorithm has to read for
the window's queries (``bench/roofline.py``, from the layout written in
the configuration file) over the chip's HBM bandwidth
(``bench/peaks.json``); the program time is the summed device time of
the probe program's operations in the traced window."""
from bench.roofline import least_seconds, point_probe_bytes, range_probe_bytes
from bench.trace import PROGRAM, op_seconds


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs = op_seconds(run.trace, PROGRAM["filter_probe"])
    if secs <= 0:
        return None
    layout = run.config["layout"]
    nbytes = sum(times * range_probe_bytes(layout, lo, hi)
                 for times, (lo, hi) in run.system.issued("range"))
    nbytes += sum(times * point_probe_bytes(layout, len(keys))
                  for times, (keys,) in run.system.issued("point"))
    return 100.0 * least_seconds(nbytes, run.peaks) / secs
