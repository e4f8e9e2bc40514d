"""encode_ms.filter: the facade's encode of a batch's typed bounds into
codes (``bloomrf/facade/encode``, ``api.py``), ms per batch, from the
program's spans on the device trace's clock."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "facade/encode")
