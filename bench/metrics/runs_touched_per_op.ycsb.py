"""runs_touched_per_op.ycsb: runs whose data blocks a scan read, per
scan of the window (the store's own ``StoreStats`` counters): what the
filters let through."""


def read(run):
    scans = run.counters.get("scans", 0)
    touched = run.counters.get("scan_runs_touched")
    if not scans or touched is None:
        return None
    return touched / scans
