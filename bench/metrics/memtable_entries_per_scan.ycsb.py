"""memtable_entries_per_scan.ycsb: memtable entries a scan walks
(``StoreStats.scan_mem_entries`` over ``StoreStats.scans``): the work of
``scan_memtable_ms.ycsb``, which grows with the memtable until a flush."""


def read(run):
    scans = run.counters.get("scans", 0)
    walked = run.counters.get("scan_mem_entries")
    if not scans or walked is None:
        return None
    return walked / scans
