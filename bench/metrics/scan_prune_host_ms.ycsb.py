"""scan_prune_host_ms.ycsb: the scan-pruning call of each batch
(``bloomrf/store/scan/prune``, ``Store._touch_masks``: the stack's
refresh after a flush, the dispatch, where a new stack shape builds its
program, and the masks' copy to the host) less the device's busy time
inside it, ms per step, from the program's spans and the device trace."""
from bench.spans import span_ms_per_step


def read(run):
    return span_ms_per_step(run, "store/scan/prune", less_device=True)
