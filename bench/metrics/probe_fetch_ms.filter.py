"""probe_fetch_ms.filter: the copy of each chunk's verdicts back to the
host once the device is done (``bloomrf/probe/fetch``,
``api.chunked_probe``), ms per batch, from the program's spans."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "probe/fetch")
