"""LSM run-store with per-run bloomRF filter blocks (DESIGN.md §10).

The paper's headline evaluation embeds bloomRF into RocksDB, where a
per-SSTable filter prunes point- and range-reads before any data-block I/O.
This package reproduces that workload standalone: a mutable
:class:`Memtable` flushes into immutable sorted :class:`Run`s, each carrying
a bloomRF filter block plus min/max fences; leveled compaction merges runs
and merges/rebuilds their filter state; and the read path batch-probes all
live runs' filters with ONE fused gather over the padded stacked state
(``store.scan_plane``, layouts as data) before touching any run's data.
"""
from .compaction import merge_filter_state, merge_sorted_runs
from .faults import FaultPlan, InjectedCrash, fault_seed_from_env
from .integrity import read_manifest, run_checksums, write_manifest
from .memtable import TOMBSTONE, Memtable
from .run import Run
from .store import Store, StoreConfig, StoreStats
from .wal import WAL_FILENAME, Wal

__all__ = [
    "Memtable",
    "TOMBSTONE",
    "Run",
    "Store",
    "StoreConfig",
    "StoreStats",
    "merge_sorted_runs",
    "merge_filter_state",
    "Wal",
    "WAL_FILENAME",
    "FaultPlan",
    "InjectedCrash",
    "fault_seed_from_env",
    "run_checksums",
    "read_manifest",
    "write_manifest",
]
