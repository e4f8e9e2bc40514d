"""Driver of the LSM store deployment (``placement="store"``).

Set-up makes ``recordcount`` distinct uniform keys and records of
``fieldcount`` fields of ``fieldlength`` bytes from the seed, opens the
store through ``repro.open_filter`` with the configuration's settings (a
write-ahead log in a fresh temporary directory when ``durability`` is
``"wal"``), and loads every record through ``put``.  A step issues its
scans as one ``scan_many``, its reads as one ``get_many``, then its
writes through ``put``; a write counts as acknowledged when ``put``
returns.

The check replays the window in order against ``reference/kv_store``:
every scan and read must equal the reference's answer at that point (the
writes of earlier steps included), and every acknowledged write of the
window must be framed in the log after the load's end.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from bench import registry
from bench.traffic import Dataset, seed_words


class System:
    def __init__(self, config: dict, seed: int, tracer, log):
        self.cfg = config
        self.seed = seed
        self.tracer = tracer
        self.log = log
        self.ref = registry.load_reference(config["reference"])
        self.windows = []           # per step: (scans, reads, writes)
        self.step_flushes = []      # per step: memtable flushes in it
        self.acknowledged_keys = set()  # keys written in the window
        self.wal_dir = None
        self._counters0 = None

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> dict:
        import repro

        cfg = self.cfg
        n = int(cfg["recordcount"])
        bits = int(cfg["key_bits"])
        rng = np.random.default_rng(seed_words(self.seed) + [0x10AD])
        t0 = time.perf_counter()
        keys = np.zeros(0, np.uint64)
        while len(keys) < n:        # distinct keys, in draw order
            draw = rng.integers(0, 1 << bits, n - len(keys) + 1024,
                                dtype=np.uint64)
            keys = np.concatenate([keys, draw])
            _, first = np.unique(keys, return_index=True)
            keys = keys[np.sort(first)]
        keys = keys[:n]
        rec = int(cfg["fieldcount"]) * int(cfg["fieldlength"])
        self.data = Dataset(keys, bits, rec)
        values = self.data.new_values(rng, n)
        t_data = time.perf_counter() - t0

        spec = dict(cfg["spec"])
        if "durability" in cfg["guarantees"]:
            # the log directory is checked whatever the program writes there
            self.wal_dir = tempfile.mkdtemp(prefix="bench_wal_")
            if spec.get("durability") == "wal":
                spec["wal_dir"] = self.wal_dir
        self.store = repro.open_filter(repro.FilterSpec(**spec))
        t0 = time.perf_counter()
        put = self.store.put
        for k, v in zip(keys.tolist(), values):
            put(k, v)
        t_load = time.perf_counter() - t0
        t0 = time.perf_counter()
        if self.wal_dir is not None:
            # the load's log reaches the disk in set-up, so the window's
            # writes do not wait behind its write-back
            for name in os.listdir(self.wal_dir):
                fd = os.open(os.path.join(self.wal_dir, name), os.O_RDONLY)
                try:
                    os.fsync(fd)
                finally:
                    os.close(fd)
        t_sync = time.perf_counter() - t0
        self.loaded = (keys, values)
        st = self.store.stats
        self.log(f"loaded {n} records of {rec} bytes: {st.flushes} flushes, "
                 f"{st.compactions} compactions")
        return {"data_s": t_data, "load_s": t_load, "log_sync_s": t_sync}

    def dataset(self) -> Dataset:
        return self.data

    def warm_up(self, shapes: dict) -> None:
        keys = self.data.sorted_keys
        for b in shapes.get("scan", ()):
            lo = keys[np.linspace(0, len(keys) - 1, b).astype(np.int64)]
            self.store.scan_many(lo, lo)
        for b in shapes.get("read", ()):
            self.store.get_many(keys[:b])
        self.log(f"{self.store.n_runs} runs, {self.store.size_bits() // 8} "
                 f"bytes of filter blocks")
        self._counters0 = self._raw_counters()
        # the window's writes are framed after these offsets
        self.log_offsets = (self.ref.log_offsets(self.wal_dir)
                            if self.wal_dir else None)

    # -- the window -----------------------------------------------------------
    def step(self, step) -> None:
        span = self.tracer.span
        flushes = self.store.stats.flushes
        scans = reads = None
        if len(step.scan_lo):
            with span("scan_many"):
                scans = (step.scan_lo, step.scan_hi,
                         self.store.scan_many(step.scan_lo, step.scan_hi))
        if len(step.read_keys):
            with span("get_many"):
                reads = (step.read_keys, self.store.get_many(step.read_keys))
        if step.writes:
            put = self.store.put
            with span("put"):
                for k, v in step.writes:
                    put(k, v)
                    self.acknowledged_keys.add(k)
        self.windows.append((scans, reads, step.writes))
        self.step_flushes.append(self.store.stats.flushes - flushes)

    def _raw_counters(self) -> dict:
        """The store's own counters (``StoreStats``), as far as it has them."""
        stats = self.store.stats
        return {k: v for k, v in vars(stats).items() if isinstance(v, int)}

    def counters(self) -> dict:
        now = self._raw_counters()
        return {k: now[k] - self._counters0.get(k, 0) for k in now}

    # -- the check ------------------------------------------------------------
    def check(self, limits: dict) -> tuple:
        ref = self.ref.SortedKV(*self.loaded)
        mismatched = answers = rows = 0
        acked = []
        for scans, reads, writes in self.windows:
            if scans is not None:
                for lo, hi, got in zip(scans[0].tolist(), scans[1].tolist(),
                                       scans[2]):
                    want = ref.scan(lo, hi)
                    answers += 1
                    rows += len(want)
                    mismatched += got != want
                # a batch that answered fewer scans than it was given
                mismatched += max(len(scans[0]) - len(scans[2]), 0)
            if reads is not None:
                for k, got in zip(reads[0].tolist(), reads[1]):
                    answers += 1
                    mismatched += got != ref.get(k)
                mismatched += max(len(reads[0]) - len(reads[1]), 0)
            for k, v in writes:
                ref.put(k, v)
                acked.append((k, v))
        checks = [("mismatched_answers", mismatched)]
        if self.wal_dir is not None:
            logged = [(k, v) for op, k, v in
                      self.ref.read_log(self.wal_dir, self.log_offsets)
                      if op == "put"]
            logged_set = {}
            for k, v in logged:
                logged_set.setdefault(k, []).append(v)
            lost = sum(1 for k, v in acked
                       if not any(x == v for x in logged_set.get(k, ())))
            checks.append(("lost_writes", lost))
        out = [{"name": n, "value": v, "limit": limits[n]} for n, v in checks]
        return out, {"answers_compared": answers, "rows_compared": rows,
                     "writes_acknowledged": len(acked)}

    def close(self) -> None:
        store = getattr(self, "store", None)
        if store is not None and store.spec.durability == "wal":
            store.close()
        if self.wal_dir is not None:
            shutil.rmtree(self.wal_dir, ignore_errors=True)
