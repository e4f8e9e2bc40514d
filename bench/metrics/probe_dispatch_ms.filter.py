"""probe_dispatch_ms.filter: the call into the jitted probe for each
chunk (``bloomrf/probe/dispatch``, ``api.chunked_probe``): JAX's dispatch
on the host, ms per batch, from the program's spans."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "probe/dispatch")
