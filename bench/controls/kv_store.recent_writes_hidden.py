"""kv_store.recent_writes_hidden: scans stop seeing the writes
acknowledged in the window, as a scan that skipped the memtable (the
host path's costliest part) would: ``mismatched_answers``."""

FAILS = "mismatched_answers"


def fault(system) -> None:
    store = system.store
    scan_many = store.scan_many
    recent = system.acknowledged_keys

    def stale(los, his):
        return [[r for r in rows if r[0] not in recent]
                for rows in scan_many(los, his)]

    store.scan_many = stale
