"""The LSM run-store: memtable + leveled runs + one-gather filter probes.

Write path: ``put``/``delete`` land in the memtable; at
``memtable_limit`` entries the memtable flushes to an immutable level-0
:class:`~repro.store.run.Run` carrying a bloomRF filter block (layout
chosen from a capacity-class ladder) and min/max fences.  When level 0
exceeds ``level0_runs`` runs, leveled compaction merges them (plus the
next level's run) downward — same-class filter blocks merge with a single
``bitwise_or``, class-graduating merges re-insert through the kernels
insert path (``compaction.merge_filter_state``).

Read path: ``get``/``scan`` first consult the memtable, then probe **all**
live runs' filters at once — the per-run states are copied into one
padded flat lane vector and probed through ``store.scan_plane``, which
takes the runs' layouts as data, so a scan over R runs costs exactly ONE
fused gather over the stacked filter state regardless of R or the mix of
capacity classes (jaxpr-asserted in the test suite), and the probe
programs survive flushes and compactions.  Only runs whose fences
overlap *and* whose filter says "maybe" have their data blocks touched;
:class:`StoreStats` counts what the filters saved (skips, false-positive
reads, bytes not read).

Filters are insert-only at write time: a delete writes a tombstone
*entry* whose key is inserted like any other, so newer tombstones are
discoverable through the filters and mask older runs at read time; no
filter bit is cleared outside compaction.  With
``mutability="deletable"`` compaction fights the resulting FPR drift:
class-graduating merges *promote* source filters in place (segment
tiling, ``core/dynamic.py``) instead of replaying keys, and when a
merge's dead-entry fraction exceeds ``purge_dead_frac`` the filter is
rebuilt from the surviving keys — purging every deleted key's bits at
the natural rebuild point (DESIGN.md §12).

``filter_backend`` swaps the per-run filter: ``"bloomrf"`` (stacked
one-gather probes), ``"none"`` (min/max fences only — the pruning
baseline), or any of the host-side baselines from ``repro.filters``
(``"bloom"``, ``"prefix_bloom"``, ``"rosetta"``, ``"surf"``) for
side-by-side comparisons in ``benchmarks/store_bench.py``.

Durability (DESIGN.md §14): with ``durability="wal"`` every
``put``/``delete``/``delete_many`` appends a CRC-framed record to a
write-ahead log (``store/wal.py``) *before* the memtable acks it, and
:meth:`Store.checkpoint` publishes a checksummed snapshot + manifest via
atomic renames (``store/integrity.py``) before resetting the log —
:meth:`Store.open` recovers the acknowledged state after a crash at any
point.  Runs whose filter block fails its checksum are *quarantined*:
the probe plane (XLA and megakernel alike) degrades them to fence-only
pruning so scans stay exact (``StoreStats.degraded_probes``), because a
corrupted filter is never allowed to produce a false negative.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
import warnings
import weakref
from typing import ClassVar, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import basic_layout, key_dtype_for
from ..core.engine import _filter_for_layout
from ..kernels import FilterOps, read_vmem_budget_u32
from ..kernels.store_scan import DEFAULT_TILE as STORE_SCAN_TILE
from ..kernels.store_scan import build_run_stack, store_scan_probe
from ..obs import metrics as _obs_metrics
from ..obs import trace as _obs_trace
from .compaction import merge_filter_state, merge_sorted_runs
from .faults import FaultPlan
from .integrity import (MANIFEST_FILENAME, atomic_write_bytes, crc32_bytes,
                        read_manifest, write_manifest)
from .memtable import TOMBSTONE, Memtable
from . import scan_plane
from .run import Run
from .wal import WAL_FILENAME, Wal

__all__ = ["Store", "StoreConfig", "StoreStats"]


def _baseline_factory(name: str):
    from .. import filters as F

    return {
        "bloom": lambda bpk: F.BloomFilter(bits_per_key=bpk),
        "prefix_bloom": lambda bpk: F.PrefixBloomFilter(bits_per_key=bpk),
        "rosetta": lambda bpk: F.Rosetta(bits_per_key=bpk),
        "surf": lambda bpk: F.SuRFLite(),
    }[name]


@jax.jit
def _fence_touch_device(kmin, kmax, lo, hi):
    """Fence-only pruning plane (``filter_backend="none"``): every fenced
    run is touched."""
    lo = jnp.atleast_1d(lo)
    hi = jnp.atleast_1d(hi)
    fence = ((hi[:, None] >= kmin[None, :]) & (lo[:, None] <= kmax[None, :]))
    return fence, fence


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    d: int = 32                     # key-domain bits
    memtable_limit: int = 4096      # entries per flush (= capacity class 0)
    bits_per_key: float = 14.0
    delta: int = 6
    fanout: int = 4                 # capacity-class / level size ratio
    level0_runs: int = 4            # level-0 run count that triggers compaction
    filter_backend: str = "bloomrf"  # "bloomrf" | "none" | repro.filters name
    scan_backend: str = "auto"      # scan-pruning plane: "auto" | "kernel"
                                    # | "xla" — "kernel" runs the fused
                                    # store-scan Pallas megakernel
                                    # (kernels/store_scan.py), "xla" the
                                    # scan_plane.touch_all reference,
                                    # "auto" picks the kernel on TPU only
                                    # (interpret-mode Pallas is slow on CPU)
    use_insert_kernels: Optional[bool] = None  # filter builds through the
                                    # Pallas insert kernel (FilterOps.insert);
                                    # None = on TPU only, as scans
    value_bytes: int = 64           # per-entry data-block size for accounting
    seed: int = 0x0B100F11
    mutability: str = "insert_only"  # "insert_only" | "deletable"
    tuning: str = "static"          # "static" (capacity-class ladder only)
                                    # | "adaptive" — sample the live scan
                                    # workload and let compaction's
                                    # class-graduating rebuilds land in a
                                    # re-solved layout (repro.tune, §16)
    purge_dead_frac: float = 0.25   # deletable: dead fraction forcing a purge
    promote_max_hops: int = 1       # promote hops a filter survives before a
                                    # rebuild is forced (promotion keeps the
                                    # source class's resolution, so each hop
                                    # multiplies FPR by the source count)
    promote_density_slack: float = 1.5  # promote only when the OR-union's
                                    # per-layer density stays within this
                                    # factor of a rebuild's (compaction.py)
    durability: str = "none"        # "none" | "wal" — "wal" appends every
                                    # write to wal_dir/wal.log before acking
                                    # and enables checkpoint()/Store.open()
    wal_dir: Optional[str] = None   # durable root: WAL + snapshots + manifest
    wal_sync: str = "flush"         # "flush" (crash-safe) | "always" (fsync
                                    # per record — power-failure-safe, slow)

    def __post_init__(self):
        if not (1 <= self.d <= 64):
            raise ValueError(
                f"d must be in 1..64 (uint64 key domain), got {self.d}")
        if not self.bits_per_key > 0:
            raise ValueError(
                f"bits_per_key must be > 0, got {self.bits_per_key}")
        if self.memtable_limit < 1 or self.fanout < 2 or self.level0_runs < 1:
            raise ValueError("memtable_limit >= 1, fanout >= 2, "
                             "level0_runs >= 1 required")
        if self.mutability not in ("insert_only", "deletable"):
            raise ValueError(
                f"mutability must be 'insert_only' or 'deletable', "
                f"got {self.mutability!r}")
        if self.tuning not in ("static", "adaptive"):
            raise ValueError(f"tuning must be 'static' or 'adaptive', "
                             f"got {self.tuning!r}")
        if self.tuning == "adaptive" and self.filter_backend != "bloomrf":
            raise ValueError(
                f"tuning='adaptive' re-solves bloomRF layouts; it needs "
                f"filter_backend='bloomrf', not {self.filter_backend!r}")
        if not (0.0 < self.purge_dead_frac <= 1.0):
            raise ValueError(
                f"purge_dead_frac must be in (0, 1], got {self.purge_dead_frac}")
        if self.promote_max_hops < 0:
            raise ValueError(
                f"promote_max_hops must be >= 0, got {self.promote_max_hops}")
        if not self.promote_density_slack > 0:
            raise ValueError(f"promote_density_slack must be > 0, "
                             f"got {self.promote_density_slack}")
        if self.filter_backend not in ("bloomrf", "none"):
            try:
                _baseline_factory(self.filter_backend)
            except KeyError:
                raise ValueError(
                    f"unknown filter_backend {self.filter_backend!r}") from None
        if self.scan_backend not in ("auto", "kernel", "xla"):
            raise ValueError(f"scan_backend must be 'auto', 'kernel' or "
                             f"'xla', got {self.scan_backend!r}")
        if self.durability not in ("none", "wal"):
            raise ValueError(f"durability must be 'none' or 'wal', "
                             f"got {self.durability!r}")
        if self.durability == "wal" and not self.wal_dir:
            raise ValueError("durability='wal' requires wal_dir")
        if self.wal_sync not in ("flush", "always"):
            raise ValueError(f"wal_sync must be 'flush' or 'always', "
                             f"got {self.wal_sync!r}")


@dataclasses.dataclass
class StoreStats:
    """Counters for what the filter blocks saved on the read path.

    Field access stays plain attribute reads/writes; :meth:`snapshot`
    returns the same counters (plus derived rates) as a flat dict so the
    obs registry and the CI gates can address them by dotted path, and
    :meth:`reset` zeroes every field in place.  The :data:`DURABLE`
    subset travels inside ``Store.snapshot()`` and survives
    restore/checkpoint/recovery round-trips (DESIGN.md §15)."""

    # write-path history: durable — it describes the data the snapshot
    # carries, so it rides along (see DURABLE below)
    puts: int = 0
    deletes: int = 0
    gets: int = 0
    scans: int = 0
    flushes: int = 0
    compactions: int = 0
    or_merges: int = 0              # same-layout filter merges (bitwise OR)
    rebuild_merges: int = 0         # cross-layout merges (key re-insert)
    promote_merges: int = 0         # in-place segment-tiled class promotions
    purge_rebuilds: int = 0         # rebuilds forced by the dead-frac policy
    retunes: int = 0                # compaction rebuilds that landed in a
                                    # tuner-advised layout instead of the
                                    # capacity-class ladder's (§16)
    # point reads
    get_runs_considered: int = 0
    get_fence_skips: int = 0
    get_filter_skips: int = 0
    get_run_reads: int = 0
    get_fp_reads: int = 0           # run read, key absent
    # scans
    scan_runs_considered: int = 0
    scan_fence_skips: int = 0
    scan_filter_skips: int = 0
    scan_runs_touched: int = 0
    scan_fp_reads: int = 0          # run touched, empty slice
    scan_mem_entries: int = 0       # memtable entries walked by scans
    scan_rows_sliced: int = 0       # rows the touched runs' slices returned
    # data-block bytes
    bytes_read: int = 0
    bytes_not_read: int = 0         # skipped runs' data bytes
    # durability / degradation
    wal_appends: int = 0            # records framed before acking a write
    wal_replayed: int = 0           # records recovered at the last open
    degraded_probes: int = 0        # (query, run) cells answered fence-only
                                    # because the run is quarantined
    # the XLA scan plane (store/scan_plane.py), per stack it probes
    scan_plane_restacks: int = 0    # stacks served by the resident programs
    scan_plane_grows: int = 0       # stacks that outgrew the plane's shape
                                    # (its first stack too): new programs

    # Counters that survive Store.snapshot()/restore(): the write-path
    # history that produced the snapshotted runs.  Read-path counters,
    # wal_appends/wal_replayed and degraded_probes describe THIS
    # process's traffic and stay local.
    DURABLE: ClassVar[Tuple[str, ...]] = (
        "puts", "deletes", "flushes", "compactions", "or_merges",
        "rebuild_merges", "promote_merges", "purge_rebuilds", "retunes")

    @property
    def runs_probed_per_scan(self) -> float:
        return self.scan_runs_touched / max(self.scans, 1)

    @property
    def scan_fp_read_rate(self) -> float:
        return self.scan_fp_reads / max(self.scan_runs_touched, 1)

    @property
    def get_fp_read_rate(self) -> float:
        return self.get_fp_reads / max(self.get_run_reads, 1)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["runs_probed_per_scan"] = self.runs_probed_per_scan
        d["scan_fp_read_rate"] = self.scan_fp_read_rate
        d["get_fp_read_rate"] = self.get_fp_read_rate
        return d

    def snapshot(self) -> dict:
        """Flat counters + derived rates (the registered-family view)."""
        return self.as_dict()

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)

    def durable_snapshot(self) -> dict:
        """The DURABLE subset, as carried inside ``Store.snapshot()``."""
        return {name: int(getattr(self, name)) for name in self.DURABLE}


class Store:
    """LSM key-value store with per-run bloomRF filter blocks."""

    def __init__(self, config: Optional[StoreConfig] = None, *,
                 faults: Optional[FaultPlan] = None,
                 _warn: bool = True, _open_wal: bool = True, **kw):
        if _warn:
            from .._compat import warn_legacy

            warn_legacy("Store(StoreConfig(...))",
                        "dtype=..., placement='store', ...")
        self.cfg = config if config is not None else StoreConfig(**kw)
        self.kdtype = key_dtype_for(self.cfg.d)
        self.mem = Memtable()
        self.levels: List[List[Run]] = [[]]   # levels[0] newest-first
        self.stats = StoreStats()
        self.faults = faults                  # fault-injection seams (tests)
        self._ops: dict = {}                  # FilterOps per layout
        self._runs: List[Run] = []
        self._stack = None                    # padded device stack (scan plane)
        self._plane_shape = None              # the plane's shape; only grows
        self._kmins = self._kmaxs = None      # per-run fences, np.uint64 (R,)
        self._quar = None                     # per-run quarantine mask (R,)
        self._quar_dev = None                 # lazy device copy of _quar
        self._kstate = None                   # lazy megakernel inputs
        self._fence_dev = None                # lazy device fences (kdtype)
        self._dirty = True
        self._wal: Optional[Wal] = None
        self._seq = 0                         # checkpoint sequence number
        self._tuner = None                    # workload-adaptive tuner (§16)
        if self.cfg.tuning == "adaptive":
            from ..tune import AdaptiveTuner

            self._tuner = AdaptiveTuner(self.cfg.d, seed=self.cfg.seed)
        if _obs_metrics.enabled():            # late joiners: register_obs()
            self.register_obs()
        if self.cfg.durability == "wal" and _open_wal:
            os.makedirs(self.cfg.wal_dir, exist_ok=True)
            wal_path = os.path.join(self.cfg.wal_dir, WAL_FILENAME)
            has_state = (
                os.path.exists(os.path.join(self.cfg.wal_dir,
                                            MANIFEST_FILENAME))
                or (os.path.exists(wal_path)
                    and os.path.getsize(wal_path) > 0))
            if has_state:
                raise ValueError(
                    f"{self.cfg.wal_dir!r} already holds store state; "
                    f"use Store.open({self.cfg.wal_dir!r}) to recover it")
            self._wal = Wal(wal_path, sync=self.cfg.wal_sync).open_for_append()

    def _fault(self, point: str) -> None:
        """Pass through a named fault-injection seam (no-op without a plan)."""
        if self.faults is not None:
            self.faults.hit(point)

    def register_obs(self, family: str = "store") -> str:
        """Join the obs registry as a metric family (DESIGN.md §15).

        The registry holds only a weak reference — a collected store
        drops out of the next ``snapshot()``.  Returns the assigned
        family name (auto-suffixed when taken).  Called automatically at
        construction when observability is already enabled."""
        sref = weakref.ref(self)
        return _obs_metrics.registry().register_family(
            family,
            lambda: (lambda s: None if s is None
                     else s.stats.snapshot())(sref()))

    # ------------------------------------------------------------------
    # capacity classes and filter construction
    # ------------------------------------------------------------------
    def class_capacity(self, cls: int) -> int:
        return self.cfg.memtable_limit * self.cfg.fanout ** cls

    def class_layout(self, n_keys: int):
        """Layout of the smallest capacity class that fits ``n_keys``."""
        cls = 0
        while self.class_capacity(cls) < n_keys:
            cls += 1
        return basic_layout(self.cfg.d, self.class_capacity(cls),
                            self.cfg.bits_per_key,
                            delta=min(self.cfg.delta, self.cfg.d),
                            seed=self.cfg.seed)

    def _build_filter(self, layout, keys: np.ndarray) -> jnp.ndarray:
        """Bulk filter build; the compaction rebuild path lands here too.

        Keys are padded to a power-of-two count by repeating one of them
        (inserting a key twice sets no new bit), so each layout compiles
        for a few shapes, not for every run length."""
        n = len(keys)
        if n:
            keys = np.concatenate(
                [keys, np.repeat(keys[:1], (1 << (n - 1).bit_length()) - n)])
        kj = jnp.asarray(keys, self.kdtype)
        kernels = self.cfg.use_insert_kernels
        if kernels is None:
            kernels = jax.default_backend() == "tpu"
        if kernels and layout.d <= 32:
            if layout not in self._ops:
                self._ops[layout] = FilterOps(layout, _warn=False)
            ops = self._ops[layout]
            return ops.insert(ops.init_state(), kj)
        return _filter_for_layout(layout).build(kj)

    def _make_run(self, keys: np.ndarray, vals: list, tombs: np.ndarray,
                  level: int) -> Run:
        layout = self.class_layout(len(keys))
        if self._tuner is not None:
            # flushes reuse the class's standing retune decision (no
            # re-solve here) so fresh runs join the tuned layout and
            # same-class compactions keep merging with a free OR
            layout = self._tuner.cached_layout(layout) or layout
        state = alt = None
        if self.cfg.filter_backend == "bloomrf":
            state = self._build_filter(layout, keys)
        elif self.cfg.filter_backend != "none":
            alt = _baseline_factory(self.cfg.filter_backend)(
                self.cfg.bits_per_key)
            alt.build(keys)
        return Run(keys, vals, tombs, level, layout, state, alt=alt)

    # ------------------------------------------------------------------
    # write path
    # ------------------------------------------------------------------
    def _check_key(self, key: int) -> int:
        key = int(key)
        if not (0 <= key < (1 << self.cfg.d)):
            raise ValueError(f"key {key} outside the {self.cfg.d}-bit domain")
        return key

    def _wal_append(self, op: str, key, value=None) -> None:
        """Frame a record before the memtable acks (durable stores only)."""
        if self._wal is None:
            return
        self._fault("wal.append")
        self._wal.append(op, key, value)
        self.stats.wal_appends += 1

    def put(self, key: int, value) -> None:
        key = self._check_key(key)
        self._wal_append("put", key, value)
        self.mem.put(key, value)
        self.stats.puts += 1
        if len(self.mem) >= self.cfg.memtable_limit:
            self.flush()

    def delete(self, key: int) -> None:
        key = self._check_key(key)
        self._wal_append("del", key)
        self.mem.delete(key)
        self.stats.deletes += 1
        if len(self.mem) >= self.cfg.memtable_limit:
            self.flush()

    def delete_many(self, keys) -> None:
        """Batched deletes: every tombstone lands in the memtable before the
        single flush decision, so a large eviction sweep triggers at most one
        flush (plus its own compaction cascade) instead of one per
        ``memtable_limit`` keys interleaved with the caller's scan.

        Durability-wise the batch is atomic: ONE ``"delm"`` WAL frame
        covers all keys, so replay applies the whole sweep or none of it
        (a torn frame was never acked)."""
        keys = [self._check_key(k) for k in keys]
        self._wal_append("delm", keys)
        for key in keys:
            self.mem.delete(key)
        self.stats.deletes += len(keys)
        if len(self.mem) >= self.cfg.memtable_limit:
            self.flush()

    def flush(self) -> None:
        """Freeze the memtable into a new level-0 run."""
        if len(self.mem) == 0:
            return
        with _obs_trace.span("store/flush"):
            keys, vals, tombs = self.mem.sorted_entries()
            run = self._make_run(keys, vals, tombs, 0)
            run.checksums()             # cache the build-time reference
            self._fault("flush.after_run")
            self.levels[0].insert(0, run)
            self.mem.clear()
            self.stats.flushes += 1
            self._dirty = True
        self._maybe_compact()

    # ------------------------------------------------------------------
    # compaction
    # ------------------------------------------------------------------
    def _maybe_compact(self) -> None:
        if len(self.levels[0]) > self.cfg.level0_runs:
            self.compact(0)
        lvl = 1
        while lvl < len(self.levels):
            runs = self.levels[lvl]
            if runs and len(runs[0]) > self.class_capacity(lvl):
                self.compact(lvl)
            lvl += 1

    def compact(self, level: int) -> None:
        """Merge every run at ``level`` (plus the next level's run) down.

        Crash-atomic: the merged run — keys, values, filter state, and its
        checksums — is fully built *before* the level lists are swapped,
        so a crash mid-compaction (the ``compact.before_swap`` fault seam)
        leaves every source run live and consistent."""
        if level >= len(self.levels) or not self.levels[level]:
            return
        with _obs_trace.span("store/compact"):
            self._compact_inner(level)

    def _compact_inner(self, level: int) -> None:
        if level + 1 >= len(self.levels):
            self.levels.append([])
        sources = self.levels[level] + self.levels[level + 1]
        bottom = not any(self.levels[lv] for lv in
                         range(level + 2, len(self.levels)))
        keys, vals, tombs = merge_sorted_runs(sources,
                                              drop_tombstones=bottom)
        if len(keys) == 0:          # everything tombstoned away
            self._fault("compact.before_swap")
            self.levels[level] = []
            self.levels[level + 1] = []
            self.stats.compactions += 1
            self._dirty = True
            return
        target_layout = self.class_layout(len(keys))
        retuned = False
        if self._tuner is not None:
            # THE retune point (§16): a class-graduating merge is already
            # paying for a rebuild, so consult the solver and re-insert
            # into the tuned layout instead of the ladder's
            tuned = self._tuner.advise_layout(target_layout, len(keys))
            retuned = tuned != target_layout
            target_layout = tuned
        state = alt = None
        if self.cfg.filter_backend == "bloomrf":
            # fraction of merged entries that did not survive (shadowed
            # duplicates + dropped tombstones): the bits those entries set
            # are dead weight in an OR/promote-merged filter
            n_in = sum(len(r) for r in sources)
            dead_frac = 1.0 - len(keys) / n_in
            deletable = self.cfg.mutability == "deletable"
            # cap promotion depth: a promoted filter still answers at its
            # source class's resolution, so hop-on-hop promotion compounds
            # FPR; once any source has used its hops, rebuild fresh
            hops = max((r.promotions for r in sources), default=0)
            state, how = merge_filter_state(
                sources, target_layout, keys, self._build_filter,
                dead_frac=dead_frac,
                purge_dead_frac=(self.cfg.purge_dead_frac if deletable
                                 else None),
                allow_promote=deletable
                and hops < self.cfg.promote_max_hops,
                promote_density_slack=self.cfg.promote_density_slack)
            counter = {"or": "or_merges", "promote": "promote_merges",
                       "rebuild": "rebuild_merges", "purge": "purge_rebuilds"}
            setattr(self.stats, counter[how],
                    getattr(self.stats, counter[how]) + 1)
            if retuned and how in ("rebuild", "purge"):
                # only count retunes that actually re-inserted into the
                # tuned layout here; an OR over already-tuned sources
                # means an earlier compaction/flush did the work
                self.stats.retunes += 1
            promotions = {"or": hops, "promote": hops + 1}.get(how, 0)
        elif self.cfg.filter_backend != "none":
            alt = _baseline_factory(self.cfg.filter_backend)(
                self.cfg.bits_per_key)
            alt.build(keys)
            self.stats.rebuild_merges += 1
            promotions = 0
        else:
            promotions = 0
        new_run = Run(keys, vals, tombs, level + 1, target_layout, state,
                      alt=alt, promotions=promotions)
        new_run.checksums()             # checksummed before it goes live
        self._fault("compact.before_swap")
        self.levels[level] = []
        self.levels[level + 1] = [new_run]
        self.stats.compactions += 1
        self._dirty = True

    # ------------------------------------------------------------------
    # stacked filter probes (the one-gather read path)
    # ------------------------------------------------------------------
    def live_runs(self) -> List[Run]:
        """All runs, newest precedence first (L0 newest-first, then down)."""
        self._refresh()
        return self._runs

    def _refresh(self) -> None:
        if not self._dirty:
            return
        with _obs_trace.span("store/refresh"):
            self._runs = [r for lvl in self.levels for r in lvl]
            self._stack = self._kstate = None
            self._fence_dev = self._quar_dev = None
            self._kmins = np.asarray([r.kmin for r in self._runs], np.uint64)
            self._kmaxs = np.asarray([r.kmax for r in self._runs], np.uint64)
            self._quar = np.asarray([r.quarantined for r in self._runs], bool)
            self._dirty = False

    def _plane_floor(self) -> scan_plane.PlaneShape:
        """The most the capacity-class ladder stacks at today's depth:
        ``level0_runs`` class-0 runs and one run per lower level at its
        largest class.  Sizing the plane to it keeps flushes and
        compactions inside a level on one compiled shape; only a new level
        (or a stack off the ladder) grows it."""
        below = len(self.levels) - 1
        c0 = self.class_layout(1)
        lanes = self.cfg.level0_runs * c0.total_u32 + sum(
            self.class_layout(self.class_capacity(lv)).total_u32
            for lv in range(1, below + 1))
        return scan_plane.PlaneShape(self.cfg.level0_runs + below, lanes + 1,
                                     c0.k, 1)

    def _run_stack(self) -> scan_plane.RunStack:
        """The XLA plane's padded device stack, built once per refresh.

        A quarantined run may have no decodable state at all — its lanes
        stay zero and the quarantine mask forces its verdict to "maybe",
        so the zeros are never trusted."""
        if self._stack is None:
            with _obs_trace.span("store/refresh"):
                layouts = [r.layout for r in self._runs]
                shape = scan_plane.PlaneShape.of(layouts).cover(
                    self._plane_floor())
                if self._plane_shape is not None:
                    shape = shape.cover(self._plane_shape)
                if shape == self._plane_shape:
                    self.stats.scan_plane_restacks += 1
                else:
                    self.stats.scan_plane_grows += 1
                self._plane_shape = shape
                self._stack = scan_plane.stack_runs(
                    layouts, [r.state for r in self._runs], self._kmins,
                    self._kmaxs, self._quar, shape)
        return self._stack

    def _quar_device(self):
        """Device quarantine mask, or None when no run is quarantined."""
        if not self._quar.any():
            return None
        if self._quar_dev is None:
            self._quar_dev = jnp.asarray(self._quar)
        return self._quar_dev

    def _fence_mask(self, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
        """(B, R) bool: query interval overlaps the run's [kmin, kmax]."""
        return ((hi[:, None] >= self._kmins[None, :])
                & (lo[:, None] <= self._kmaxs[None, :]))

    def _filter_mask(self, lo: np.ndarray, hi: np.ndarray,
                     point: bool) -> np.ndarray:
        """(B, R) bool filter verdicts (True = run may hold a match).

        Quarantined rows answer "maybe" unconditionally — their filter
        block failed its checksum, so its verdicts are untrusted."""
        if self.cfg.filter_backend == "none":
            return np.ones((len(lo), len(self._runs)), bool)
        if self.cfg.filter_backend == "bloomrf":
            # the plane answers (B, rows) with the padded rows False; the
            # live rows are the first R columns
            stack = self._run_stack()
            if point:
                out = np.asarray(scan_plane.point_all(
                    stack, jnp.asarray(lo, self.kdtype)))
            else:
                with _obs_trace.span("store/scan/dispatch"):
                    v = scan_plane.range_all(stack,
                                             jnp.asarray(lo, self.kdtype),
                                             jnp.asarray(hi, self.kdtype))
                with _obs_trace.span("store/scan/fetch"):
                    out = np.asarray(v)
            out = out[:, :len(self._runs)]
        else:
            cols = [r.alt.point(lo) if point else r.alt.range(lo, hi)
                    for r in self._runs]
            out = np.stack(cols, axis=1)
        if self._quar.any():
            out = out | self._quar[None, :]
        return out

    def probe_runs(self, lo, hi, point: bool = False
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched pruning verdicts over all live runs.

        Returns ``(fence, filt)``, each (B, R) bool — the fence overlap
        mask and the filter verdicts.  A run is touched only where both
        are True.  One fused gather for the whole batch x run matrix when
        the backend is bloomRF."""
        self._refresh()
        lo = np.atleast_1d(np.asarray(lo, np.uint64))
        hi = lo if point else np.atleast_1d(np.asarray(hi, np.uint64))
        if not self._runs:
            z = np.zeros((len(lo), 0), bool)
            return z, z
        fence = self._fence_mask(lo, hi)
        # Filter probes run in the filter's d-bit dtype: clamp bounds into
        # the domain first, or an out-of-domain `hi` would wrap under the
        # dtype cast and the (min/max-normalised) probe would answer the
        # wrong interval — a false negative the fences don't catch.  The
        # clamped interval is exactly `query ∩ domain`; queries entirely
        # above the domain are already fenced off (kmax <= dmax < lo).
        dmax = np.uint64((1 << self.cfg.d) - 1)
        filt = self._filter_mask(np.minimum(lo, dmax), np.minimum(hi, dmax),
                                 point)
        if self._quar.any():
            self.stats.degraded_probes += int(
                (fence & self._quar[None, :]).sum())
        return fence, filt

    # ------------------------------------------------------------------
    # fused scan-pruning plane (fence ∧ filter in one device step)
    # ------------------------------------------------------------------
    def _scan_kernel_mode(self) -> str:
        """Resolve ``cfg.scan_backend`` for the current run stack.

        The megakernel handles bloomRF stacks in the uint32 key domain
        (the capacity-class ladder never emits exact-bitmap layouts, so
        d <= 32 is the only real constraint); everything else takes the
        XLA-exact path.  ``auto`` picks the kernel only on a real TPU —
        interpret-mode Pallas on CPU is for parity tests, not speed."""
        if (self.cfg.scan_backend == "xla"
                or self.cfg.filter_backend != "bloomrf"
                or self.cfg.d > 32 or not self._runs):
            return "xla"
        if self.cfg.scan_backend == "kernel":
            return "kernel"
        return "kernel" if jax.default_backend() == "tpu" else "xla"

    def _kernel_inputs(self):
        """Scan-kernel operands for the live stack, built once per
        refresh: the padded ``(R, rowpad)`` run stack, uint32 device
        fences, and the gather tier: ``resident`` while the whole stack
        fits the VMEM budget, else the stack stays in HBM."""
        if self._kstate is None:
            layouts = tuple(r.layout for r in self._runs)
            stack = build_run_stack([r.state for r in self._runs])
            resident = stack.size <= read_vmem_budget_u32()
            self._kstate = (layouts, stack,
                            jnp.asarray(self._kmins, jnp.uint32),
                            jnp.asarray(self._kmaxs, jnp.uint32), resident)
        return self._kstate

    def _touch_masks(self, lo: np.ndarray,
                     hi: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Host scan pruning: ``(fence, touch)`` (B, R) bool.

        ``touch = fence & filter-maybe`` — the runs whose data blocks a
        scan must read.  Dispatches per ``_scan_kernel_mode``: one fused
        Pallas call, or the XLA fence+probe reference (bit-identical)."""
        self._refresh()
        if not self._runs:
            z = np.zeros((len(lo), 0), bool)
            return z, z
        if self._scan_kernel_mode() == "kernel":
            # a kernel failure fails the scan: there is no silent retry
            # through the XLA plane, so a TPU scan that returns did run
            # the kernel
            self._fault("kernel.dispatch")
            dmax = np.uint64((1 << self.cfg.d) - 1)
            layouts, stack, kmin_d, kmax_d, resident = self._kernel_inputs()
            with _obs_trace.span("store/scan/dispatch"):
                f, t = store_scan_probe(
                    layouts, stack, kmin_d, kmax_d,
                    jnp.asarray(np.minimum(lo, dmax), jnp.uint32),
                    jnp.asarray(np.minimum(hi, dmax), jnp.uint32),
                    STORE_SCAN_TILE, resident, None, self._quar_device())
            with _obs_trace.span("store/scan/fetch"):
                fence, touch = np.asarray(f), np.asarray(t)
            # the uint32 clamp is exact for every in-domain `lo` (kmin,
            # kmax <= dmax); intervals entirely above the domain must be
            # fenced off on the host instead (kmax <= dmax < lo)
            dead = lo > dmax
            if dead.any():
                fence, touch = fence.copy(), touch.copy()
                fence[dead] = touch[dead] = False
            if self._quar.any():
                self.stats.degraded_probes += int(
                    (fence & self._quar[None, :]).sum())
            return fence, touch
        fence, filt = self.probe_runs(lo, hi, point=False)
        return fence, fence & filt

    def scan_probe_device(self, lo, hi) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Device-resident scan pruning: ``(fence, touch)`` (B, R) bool
        jax arrays, no host round-trip — the YCSB device driver's probe
        plane.  Bounds must already lie inside the d-bit key domain
        (``scan_many`` handles out-of-domain clamping on the host).

        One fused megakernel call in ``kernel`` mode; the jit'd
        ``scan_plane.touch_all`` (still one fused gather) in ``xla``
        mode, its padded rows sliced off on the device; fence-only
        verdicts for ``filter_backend="none"``."""
        self._refresh()
        if _obs_metrics.enabled():
            # host-side batch odometer only: the dispatch stays async and
            # nothing syncs — the ≤1.05x obs-overhead gate times this path
            _obs_metrics.registry().counter(
                "store/scan_probe_batches").add(1)
        lo = jnp.atleast_1d(lo)
        if not self._runs:
            z = jnp.zeros((lo.shape[0], 0), bool)
            return z, z
        if self._scan_kernel_mode() == "kernel":
            self._fault("kernel.dispatch")
            layouts, stack, kmin_d, kmax_d, resident = self._kernel_inputs()
            return store_scan_probe(layouts, stack, kmin_d, kmax_d,
                                    lo, hi, STORE_SCAN_TILE, resident, None,
                                    self._quar_device())
        lo = jnp.asarray(lo, self.kdtype)
        hi = jnp.asarray(hi, self.kdtype)
        if self.cfg.filter_backend == "bloomrf":
            fence, touch = scan_plane.touch_all(self._run_stack(), lo, hi)
            R = len(self._runs)
            return fence[:, :R], touch[:, :R]
        if self._fence_dev is None:
            self._fence_dev = (jnp.asarray(self._kmins, self.kdtype),
                               jnp.asarray(self._kmaxs, self.kdtype))
        kmin_d, kmax_d = self._fence_dev
        if self.cfg.filter_backend == "none":
            fence, touch = _fence_touch_device(kmin_d, kmax_d, lo, hi)
            return fence, touch
        raise ValueError(
            f"device scan probing needs the 'bloomrf' or 'none' backend, "
            f"not {self.cfg.filter_backend!r} (host-side baseline)")

    # ------------------------------------------------------------------
    # read path
    # ------------------------------------------------------------------
    def get(self, key: int):
        """Point lookup; None when absent or deleted."""
        return self.get_many(np.asarray([self._check_key(key)], np.uint64))[0]

    def get_many(self, keys) -> list:
        """Batched point lookups: one fused filter gather for the batch."""
        keys = np.atleast_1d(np.asarray(keys, np.uint64))
        if self._tuner is not None:
            self._tuner.observe_points(len(keys))
        with _obs_trace.span("store/get"):
            return self._get_many_inner(keys)

    def _get_many_inner(self, keys: np.ndarray) -> list:
        st = self.stats
        st.gets += len(keys)
        fence, filt = self.probe_runs(keys, keys, point=True)
        dbytes = np.asarray([r.data_bytes(self.cfg.value_bytes)
                             for r in self._runs], np.int64)
        out = []
        for b, key in enumerate(keys):
            found, v = self.mem.get(int(key))
            if found:
                out.append(None if v is TOMBSTONE else v)
                continue
            result = None
            R = len(self._runs)
            st.get_runs_considered += R
            st.get_fence_skips += int((~fence[b]).sum())
            st.get_filter_skips += int((fence[b] & ~filt[b]).sum())
            # skipped runs save their data blocks on the point path too —
            # mirror of the _scan_one credit, so bytes_not_read covers
            # point-heavy workloads instead of understating savings
            st.bytes_not_read += int(dbytes[~(fence[b] & filt[b])].sum())
            for r_idx in np.flatnonzero(fence[b] & filt[b]):
                run = self._runs[r_idx]
                st.get_run_reads += 1
                st.bytes_read += run.data_bytes(self.cfg.value_bytes)
                hit, val, tomb = run.lookup(int(key))
                if hit:
                    result = None if tomb else val
                    break
                st.get_fp_reads += 1
            out.append(result)
        return out

    def scan(self, lo: int, hi: int) -> list:
        """All live (key, value) pairs with lo <= key <= hi, ascending."""
        return self.scan_many([lo], [hi])[0]

    def scan_many(self, los, his) -> list:
        """Batched scans: the whole pruning plane (fence + filter) in one
        device dispatch for the batch — a single megakernel call or one
        fused XLA gather, per ``StoreConfig.scan_backend``."""
        los = np.atleast_1d(np.asarray(los, np.uint64))
        his = np.atleast_1d(np.asarray(his, np.uint64))
        if self._tuner is not None:
            # host-side workload sampling (numpy histogram + reservoir);
            # the device probe dispatch below stays untouched
            self._tuner.observe_scan(los, his)
        with _obs_trace.span("store/scan"):
            with _obs_trace.span("store/scan/prune"):
                fence, touch = self._touch_masks(los, his)
            # no scan changes the memtable: each one walks all of it
            self.stats.scan_mem_entries += len(self.mem) * len(los)
            return [self._scan_one(int(lo), int(hi), fence[b], touch[b])
                    for b, (lo, hi) in enumerate(zip(los, his))]

    def _scan_one(self, lo: int, hi: int, fence: np.ndarray,
                  touch: np.ndarray) -> list:
        st = self.stats
        st.scans += 1
        seen = set()
        out = {}
        span = _obs_trace.span
        with span("store/scan/memtable"):
            for k, v in self.mem.items():
                if lo <= k <= hi:
                    seen.add(k)
                    if v is not TOMBSTONE:
                        out[k] = v
        with span("store/scan/runs"):
            sliced = 0
            R = len(self._runs)
            st.scan_runs_considered += R
            st.scan_fence_skips += int((~fence).sum())
            st.scan_filter_skips += int((fence & ~touch).sum())
            for r_idx, run in enumerate(self._runs):
                if not touch[r_idx]:
                    st.bytes_not_read += run.data_bytes(self.cfg.value_bytes)
                    continue
                st.scan_runs_touched += 1
                st.bytes_read += run.data_bytes(self.cfg.value_bytes)
                ks, vs, tbs = run.slice(lo, hi)
                sliced += len(ks)
                if len(ks) == 0:
                    st.scan_fp_reads += 1
                    continue
                for k, v, t in zip(ks, vs, tbs):
                    k = int(k)
                    if k in seen:
                        continue        # masked by a newer source
                    seen.add(k)
                    if not t:
                        out[k] = v
            st.scan_rows_sliced += sliced
            return sorted(out.items())

    # ------------------------------------------------------------------
    # introspection / snapshots
    # ------------------------------------------------------------------
    @property
    def n_runs(self) -> int:
        return sum(len(lvl) for lvl in self.levels)

    def filter_bits(self) -> int:
        return sum(r.layout.total_bits for r in self.live_runs()
                   if r.state is not None)

    def quarantined_runs(self) -> List[Run]:
        """Live runs whose filter block failed its checksum."""
        return [r for r in self.live_runs() if r.quarantined]

    def snapshot(self, flush_first: bool = True) -> dict:
        """Compressed snapshot of the store's full state.

        The memtable is not serializable as such, so by default the store
        flushes it into a level-0 run first — a snapshot that silently
        dropped unflushed writes was this API's original sin.  Pass
        ``flush_first=False`` to snapshot only the frozen runs; without a
        WAL to re-cover the memtable that choice warns, because the
        unflushed entries exist nowhere else.

        v3 snapshots carry per-run component CRCs (``Run.pack``);
        ``restore`` accepts v1/v2 too (unverified).
        """
        if flush_first:
            self.flush()
        elif len(self.mem) and self._wal is None:
            warnings.warn(
                f"snapshot(flush_first=False) with {len(self.mem)} unflushed "
                f"memtable entries and no WAL: those writes are not in the "
                f"snapshot and will not survive a restore",
                RuntimeWarning, stacklevel=2)
        snap = {"schema": "bloomrf-store/v3",
                "config": dataclasses.asdict(self.cfg),
                "stats": self.stats.durable_snapshot(),
                "levels": [[r.pack() for r in lvl] for lvl in self.levels]}
        if self._tuner is not None:
            # the fitted workload model (bloomrf-workload/v1) rides along
            # so a reopened store resumes tuning from its sample
            snap["workload"] = self._tuner.to_dict()
        return snap

    @classmethod
    def restore(cls, snap: dict) -> "Store":
        """Validated inverse of :meth:`snapshot` (in-memory only: a durable
        config's WAL is NOT attached here — recover through
        :meth:`Store.open` instead).

        Malformed or corrupted input raises an actionable ``ValueError``
        (never a segfault or a silent mis-restore); a run whose *filter
        block* alone is corrupt restores quarantined (see ``Run.unpack``).
        """
        if not isinstance(snap, dict):
            raise ValueError(f"store snapshot must be a dict, "
                             f"got {type(snap).__name__}")
        if snap.get("schema") not in ("bloomrf-store/v1", "bloomrf-store/v2",
                                      "bloomrf-store/v3"):
            raise ValueError(f"not a store snapshot: {snap.get('schema')!r}")
        cfg_enc = snap.get("config")
        if not isinstance(cfg_enc, dict):
            raise ValueError("store snapshot: 'config' must be a dict")
        try:
            cfg = StoreConfig(**cfg_enc)
        except (TypeError, ValueError) as e:
            raise ValueError(f"store snapshot: bad config: {e}") from e
        store = cls(cfg, _warn=False, _open_wal=False)
        levels_enc = snap.get("levels")
        if (not isinstance(levels_enc, list)
                or not all(isinstance(lvl, list) for lvl in levels_enc)):
            raise ValueError("store snapshot: 'levels' must be a list of "
                             "run lists")
        store.levels = [[Run.unpack(enc) for enc in lvl]
                        for lvl in levels_enc]
        if not store.levels:
            store.levels = [[]]
        if store.cfg.filter_backend not in ("bloomrf", "none"):
            for lvl in store.levels:     # baselines don't snapshot: rebuild
                for r in lvl:
                    r.alt = _baseline_factory(store.cfg.filter_backend)(
                        store.cfg.bits_per_key)
                    r.alt.build(r.keys)
        stats_enc = snap.get("stats")    # optional: absent in v1/v2 or
        if stats_enc is not None:        # pre-§15 v3 snapshots
            # kernel_fallbacks: a retired counter older snapshots carry
            stats_enc = ({k: v for k, v in stats_enc.items()
                          if k != "kernel_fallbacks"}
                         if isinstance(stats_enc, dict) else stats_enc)
            if (not isinstance(stats_enc, dict)
                    or not set(stats_enc) <= set(StoreStats.DURABLE)
                    or not all(isinstance(v, int) and not isinstance(v, bool)
                               and v >= 0 for v in stats_enc.values())):
                raise ValueError(
                    "store snapshot: 'stats' must map durable counter "
                    "names to non-negative ints")
            for k, v in stats_enc.items():
                setattr(store.stats, k, v)
        wl_enc = snap.get("workload")    # optional: adaptive-tuned stores
        if wl_enc is not None:
            from ..tune import WorkloadModel

            try:
                model = WorkloadModel.from_dict(wl_enc)
            except ValueError as e:
                raise ValueError(
                    f"store snapshot: bad workload model: {e}") from e
            if store._tuner is not None:
                if model.d != store.cfg.d:
                    raise ValueError(
                        f"store snapshot: workload model d={model.d} does "
                        f"not match config d={store.cfg.d}")
                store._tuner.load(wl_enc)
        store._dirty = True
        return store

    # ------------------------------------------------------------------
    # durability: checkpoint / recovery / scrub (DESIGN.md §14)
    # ------------------------------------------------------------------
    def checkpoint(self) -> str:
        """Make the current state durable; returns the snapshot path.

        Protocol: flush the memtable, write ``snapshot-<seq>.bin``
        atomically (temp file + rename), publish the self-checksummed
        manifest naming it (also atomic), and only then reset the WAL and
        GC older snapshots.  A crash at any point leaves a recoverable
        directory: before the manifest rename the old checkpoint + full
        WAL still recover everything; after it, WAL replay is idempotent
        (last-write-wins), so replaying records the snapshot already
        holds changes nothing."""
        if self._wal is None:
            raise ValueError("checkpoint() requires durability='wal' "
                             "(open the store with a durable StoreConfig "
                             "or Store.open)")
        with _obs_trace.span("store/checkpoint"):
            self.flush()
            snap = self.snapshot(flush_first=False)
            blob = pickle.dumps(snap, protocol=pickle.HIGHEST_PROTOCOL)
            self._seq += 1
            name = f"snapshot-{self._seq:08d}.bin"
            path = os.path.join(self.cfg.wal_dir, name)
            atomic_write_bytes(path, blob, fault=self.faults,
                               fault_point="snapshot.before_rename")
            write_manifest(self.cfg.wal_dir,
                           {"snapshot": name, "crc32": crc32_bytes(blob),
                            "seq": self._seq},
                           fault=self.faults)
            self._wal.reset()
            self._gc_snapshots(keep=name)
            return path

    def _gc_snapshots(self, keep: str) -> None:
        """Drop superseded/orphaned snapshot files (best-effort)."""
        for fn in os.listdir(self.cfg.wal_dir):
            if (fn.startswith("snapshot-") and fn.endswith(".bin")
                    and fn != keep):
                try:
                    os.unlink(os.path.join(self.cfg.wal_dir, fn))
                except OSError:
                    pass

    @classmethod
    def open(cls, wal_dir: str, config: Optional[StoreConfig] = None, *,
             faults: Optional[FaultPlan] = None) -> "Store":
        """Open (or crash-recover) the durable store rooted at ``wal_dir``.

        Recovery trusts nothing unverified: the manifest's own CRC, then
        the snapshot file's CRC against the manifest's record, then every
        run's component CRCs (``Run.unpack``).  After the snapshot loads,
        the WAL is healed of any torn tail and its records replay into
        the memtable — replay is idempotent, so records the snapshot
        already holds are harmless.  ``config`` seeds a fresh store when
        no checkpoint exists yet (its ``durability``/``wal_dir`` are
        forced to this directory either way)."""
        manifest = read_manifest(wal_dir)    # ValueError on corruption
        if manifest is not None:
            name = manifest.get("snapshot")
            path = os.path.join(wal_dir, str(name))
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError as e:
                raise ValueError(f"manifest names snapshot {name!r} but it "
                                 f"cannot be read: {e}") from e
            if crc32_bytes(blob) != int(manifest.get("crc32", -1)):
                raise ValueError(
                    f"snapshot {name!r} fails its manifest CRC — torn write "
                    f"or bit rot; restore from a previous checkpoint")
            try:
                snap = pickle.loads(blob)
            except Exception as e:
                raise ValueError(f"snapshot {name!r} passed its CRC but "
                                 f"does not unpickle: {e}") from e
            store = cls.restore(snap)
            store.cfg = dataclasses.replace(store.cfg, durability="wal",
                                            wal_dir=wal_dir)
            store._seq = int(manifest.get("seq", 0))
        else:
            cfg = config if config is not None else StoreConfig(
                durability="wal", wal_dir=wal_dir)
            cfg = dataclasses.replace(cfg, durability="wal", wal_dir=wal_dir)
            store = cls(cfg, _warn=False, _open_wal=False)
        store.faults = faults
        os.makedirs(wal_dir, exist_ok=True)
        store._wal = Wal(os.path.join(wal_dir, WAL_FILENAME),
                         sync=store.cfg.wal_sync).open_for_append()
        store._replay_wal()
        return store

    def _replay_wal(self) -> None:
        """Re-apply every intact WAL record through the memtable.

        Records go straight into the memtable (not through ``put`` — they
        must not re-append to the log they came from) with the normal
        flush trigger, so replaying more than ``memtable_limit`` records
        rebuilds runs exactly as the live path would have.

        Replayed records re-enter the durable ``puts``/``deletes``
        counters: the restored snapshot's stats stop at checkpoint time,
        so the post-checkpoint tail must be re-counted for the durable
        totals to equal every acked write (DESIGN.md §15)."""
        n = 0
        with _obs_trace.span("wal/replay"):
            for op, key, value in self._wal.replay():
                if op == "put":
                    self.mem.put(int(key), value)
                    self.stats.puts += 1
                elif op == "del":
                    self.mem.delete(int(key))
                    self.stats.deletes += 1
                else:                   # "delm": one frame, many tombstones
                    for k in key:
                        self.mem.delete(int(k))
                    self.stats.deletes += len(key)
                n += 1
                if len(self.mem) >= self.cfg.memtable_limit:
                    self.flush()
        self.stats.wal_replayed = n

    def close(self) -> None:
        """Release the WAL file handle (the store stays readable)."""
        if self._wal is not None:
            self._wal.close()

    def scrub(self, sample_keys: int = 64, seed: int = 0) -> dict:
        """Full integrity pass over every live run.

        Re-checks each run's component CRCs against its build-time
        reference: a keys/fences/values mismatch raises (data corruption
        has no graceful mode), a filter-block mismatch quarantines the
        run in place.  Then re-asserts the no-false-negative contract on
        up to ``sample_keys`` sampled live keys per run — each must probe
        "maybe" on its own row (a quarantined row trivially does).
        Returns a report dict."""
        with _obs_trace.span("store/scrub"):
            return self._scrub_inner(sample_keys, seed)

    def _scrub_inner(self, sample_keys: int, seed: int) -> dict:
        self._refresh()
        rng = np.random.default_rng(seed)
        newly = 0
        for r in self._runs:
            res = r.verify()
            bad = [c for c in ("keys", "fences", "vals", "tombs")
                   if not res.get(c, True)]
            if bad:
                raise ValueError(
                    f"scrub: level-{r.level} run failed {bad} checksum(s) — "
                    f"data corruption; restore from a checkpoint")
            if not res.get("filter", True) and not r.quarantined:
                r.quarantined = True
                newly += 1
                self._dirty = True
        if newly:
            self._refresh()
        report = {"runs": len(self._runs),
                  "quarantined": int(sum(r.quarantined for r in self._runs)),
                  "newly_quarantined": newly,
                  "fn_checked": 0}
        for idx, r in enumerate(self._runs):
            live = r.keys[~r.tombs]
            if len(live) == 0:
                continue
            pick = (live if len(live) <= sample_keys
                    else rng.choice(live, sample_keys, replace=False))
            fence, filt = self.probe_runs(pick, pick, point=True)
            report["fn_checked"] += len(pick)
            if not (fence[:, idx] & filt[:, idx]).all():
                raise ValueError(
                    f"scrub: filter false negative on level-{r.level} run "
                    f"{idx} — filter block corrupt beyond its checksum")
        return report
