"""probe_put_ms.filter: the transfer of each chunk's bounds to the
device (``bloomrf/probe/put``, ``api.chunked_probe``), ms per batch, from
the program's spans."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "probe/put")
