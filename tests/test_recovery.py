"""Crash-safety: WAL round-trips, fault-injection recovery, quarantine.

Covers DESIGN.md §14: the append-before-ack WAL contract (zero lost
acknowledged writes across injected crashes at every seam), torn-tail
tolerance through real files, checkpoint/manifest atomicity, crash-atomic
compaction, checksum-quarantined filter blocks degrading to fence-only
pruning bit-identically in the XLA and megakernel probe paths, the
runtime pallas_call dispatch fallback, malformed-snapshot hardening, and
the Supervisor's jittered exponential backoff.
"""
import copy
import dataclasses
import os
import pickle

import numpy as np
import pytest

from repro.store import (FaultPlan, InjectedCrash, Run, Store, StoreConfig,
                         Wal, fault_seed_from_env)
from repro.store.faults import flip_filter_bits, truncate_tail
from repro.store.integrity import read_manifest, write_manifest
from repro.store.wal import WAL_FILENAME

FUZZ_SEED = fault_seed_from_env(default=0xFA17)

# every crash seam the store threads FaultPlan through (kernel.dispatch is
# exercised separately — it must be absorbed, not crash)
CRASH_SEAMS = ["wal.append", "flush.after_run", "compact.before_swap",
               "snapshot.before_rename", "manifest.before_rename"]


def durable_config(wal_dir, **kw):
    kw.setdefault("d", 16)
    kw.setdefault("memtable_limit", 32)
    kw.setdefault("level0_runs", 2)
    return StoreConfig(durability="wal", wal_dir=str(wal_dir), **kw)


# ---------------------------------------------------------------------------
# WAL unit tests (real files in tmp_path)
# ---------------------------------------------------------------------------

def test_wal_roundtrip_real_file(tmp_path):
    path = str(tmp_path / WAL_FILENAME)
    wal = Wal(path).open_for_append()
    wal.append("put", 7, "seven")
    wal.append("del", 7)
    wal.append("delm", [1, 2, 3])
    wal.close()
    # replay through a FRESH handle: everything went through real bytes
    back = Wal(path).records()
    assert back == [("put", 7, "seven"), ("del", 7, None),
                    ("delm", [1, 2, 3], None)]


def test_wal_truncated_tail_tolerated(tmp_path):
    path = str(tmp_path / WAL_FILENAME)
    wal = Wal(path).open_for_append()
    for i in range(20):
        wal.append("put", i, i * 2)
    wal.close()
    rng = np.random.default_rng(1)
    torn = truncate_tail(path, rng, max_bytes=24)
    assert torn > 0
    back = Wal(path).records()
    # the tear kills at most the trailing record(s) it bit into; every
    # record before the tear point replays intact, in order
    assert 0 < len(back) <= 20
    assert back == [("put", i, i * 2) for i in range(len(back))]
    # open_for_append heals the file back to the last intact frame
    wal2 = Wal(path).open_for_append()
    assert wal2.torn_bytes > 0
    wal2.append("put", 99, "after-heal")
    wal2.close()
    assert Wal(path).records()[-1] == ("put", 99, "after-heal")


def test_wal_garbage_tail_ignored(tmp_path):
    path = str(tmp_path / WAL_FILENAME)
    wal = Wal(path).open_for_append()
    wal.append("put", 1, "a")
    wal.close()
    with open(path, "ab") as f:       # a torn in-flight frame
        f.write(b"\xff\xff\xff\xff garbage that is not a frame")
    assert Wal(path).records() == [("put", 1, "a")]


def test_wal_reset_drops_records(tmp_path):
    wal = Wal(str(tmp_path / WAL_FILENAME)).open_for_append()
    wal.append("put", 1, "a")
    wal.reset()
    wal.append("put", 2, "b")
    wal.close()
    assert Wal(wal.path).records() == [("put", 2, "b")]


# ---------------------------------------------------------------------------
# durability: open / replay / checkpoint
# ---------------------------------------------------------------------------

def test_acked_writes_survive_crash_before_flush(tmp_path):
    cfg = durable_config(tmp_path, memtable_limit=1000)
    st = Store(cfg, _warn=False)
    for k in range(50):               # all acked, none flushed
        st.put(k, k * 3)
    st.delete(10)
    assert st.n_runs == 0             # still memtable-only
    st.close()                        # "crash": no flush, no checkpoint
    rec = Store.open(str(tmp_path))
    assert rec.stats.wal_replayed == 51
    assert rec.get(7) == 21 and rec.get(10) is None
    assert rec.get_many(np.arange(50)) == \
        [None if k == 10 else k * 3 for k in range(50)]


def test_checkpoint_then_wal_tail_recovers_both(tmp_path):
    st = Store(durable_config(tmp_path), _warn=False)
    for k in range(100):
        st.put(k, k)
    st.checkpoint()
    st.put(500, "tail")               # post-checkpoint, WAL-only
    st.delete(5)
    st.close()
    rec = Store.open(str(tmp_path))
    assert rec.stats.wal_replayed == 2
    assert rec.get(500) == "tail" and rec.get(5) is None and rec.get(50) == 50


def test_checkpoint_is_idempotent_replay(tmp_path):
    """Crash between manifest rename and WAL reset: replaying records the
    snapshot already holds must change nothing (last-write-wins)."""
    st = Store(durable_config(tmp_path), _warn=False)
    for k in range(80):
        st.put(k, ("v", k))
    faults = FaultPlan(crashes={})    # no crash: build a clean checkpoint
    st.checkpoint()
    # simulate the lost WAL reset: rewrite every pre-checkpoint record
    wal = Wal(os.path.join(str(tmp_path), WAL_FILENAME)).open_for_append()
    for k in range(80):
        wal.append("put", k, ("v", k))
    wal.close()
    rec = Store.open(str(tmp_path))
    assert rec.stats.wal_replayed == 80
    assert rec.get_many(np.arange(80)) == [("v", k) for k in range(80)]
    assert faults.fired == []


@pytest.mark.parametrize("seam", ["snapshot.before_rename",
                                  "manifest.before_rename"])
def test_checkpoint_crash_leaves_recoverable_state(tmp_path, seam):
    st = Store(durable_config(tmp_path), _warn=False,
               faults=FaultPlan(crashes={seam: 1}))
    for k in range(60):
        st.put(k, k + 1)
    with pytest.raises(InjectedCrash):
        st.checkpoint()
    st.close()
    rec = Store.open(str(tmp_path))   # WAL still holds everything acked
    assert rec.get_many(np.arange(60)) == [k + 1 for k in range(60)]
    # and a later checkpoint completes normally
    rec.checkpoint()
    rec.put(1000, "post")
    rec.close()
    rec2 = Store.open(str(tmp_path))
    assert rec2.get(1000) == "post" and rec2.get(0) == 1


def test_fresh_init_refuses_existing_state(tmp_path):
    st = Store(durable_config(tmp_path), _warn=False)
    st.put(1, "a")
    st.close()
    with pytest.raises(ValueError, match="Store.open"):
        Store(durable_config(tmp_path), _warn=False)


def test_corrupt_manifest_is_actionable(tmp_path):
    st = Store(durable_config(tmp_path), _warn=False)
    st.put(1, "a")
    st.checkpoint()
    st.close()
    mpath = os.path.join(str(tmp_path), "MANIFEST.json")
    with open(mpath, "r+b") as f:     # flip a payload byte: CRC must catch
        f.seek(os.path.getsize(mpath) // 2)
        c = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([c[0] ^ 1]))
    with pytest.raises(ValueError, match="manifest"):
        Store.open(str(tmp_path))


def test_manifest_roundtrip_and_crc(tmp_path):
    write_manifest(str(tmp_path), {"snapshot": "s-1.bin", "crc32": 5,
                                   "seq": 1})
    m = read_manifest(str(tmp_path))
    assert m["snapshot"] == "s-1.bin" and m["seq"] == 1
    assert read_manifest(str(tmp_path / "nope")) is None


# ---------------------------------------------------------------------------
# crash-atomic compaction
# ---------------------------------------------------------------------------

def test_compaction_crash_leaves_old_runs_live(tmp_path):
    cfg = durable_config(tmp_path, memtable_limit=16, level0_runs=1)
    st = Store(cfg, _warn=False,
               faults=FaultPlan(crashes={"compact.before_swap": 1}))
    keys = np.arange(0, 64, dtype=np.uint64)
    with pytest.raises(InjectedCrash):
        for k in keys:
            st.put(int(k), int(k))
    # the in-memory object survived the unwound compaction: every source
    # run must still be live and every *acked* key readable
    acked = [int(k) for k in keys if st.get(int(k)) is not None]
    assert len(acked) >= 16           # at least the first flushed batch
    st.close()
    rec = Store.open(str(tmp_path))   # and the real recovery path agrees
    for k in acked:
        assert rec.get(k) == k, k


# ---------------------------------------------------------------------------
# quarantine: degraded scans stay exact and bit-identical across backends
# ---------------------------------------------------------------------------

def _filtered_store(scan_backend="xla", seed=3):
    st = Store(StoreConfig(d=20, memtable_limit=64, level0_runs=2,
                           scan_backend=scan_backend), _warn=False)
    rng = np.random.default_rng(seed)
    for k in rng.choice(1 << 20, 500, replace=False):
        st.put(int(k), int(k) ^ 0xBEEF)
    st.flush()
    return st


def _corrupt_one_filter(snap, rng):
    """Deep-copied snapshot with one run's filter bits flipped."""
    snap2 = copy.deepcopy(snap)
    encs = [e for lvl in snap2["levels"] for e in lvl if "filter" in e]
    assert encs, "fixture produced no filtered runs"
    victim = encs[rng.integers(0, len(encs))]
    bad = flip_filter_bits(victim, rng, nbits=3)
    snap2["levels"] = [[bad if e is victim else e for e in lvl]
                       for lvl in snap2["levels"]]
    return snap2


@pytest.mark.parametrize("backend", ["xla", "kernel"])
def test_quarantined_scan_bit_identical_to_control(backend):
    rng = np.random.default_rng(7)
    base = _filtered_store()
    snap = base.snapshot()
    ctrl = Store.restore(copy.deepcopy(snap))
    hurt = Store.restore(_corrupt_one_filter(snap, rng))
    assert len(hurt.quarantined_runs()) == 1
    assert ctrl.quarantined_runs() == []
    for s in (ctrl, hurt):            # kernel path runs interpret on CPU
        s.cfg = dataclasses.replace(s.cfg, scan_backend=backend)
    los = np.arange(0, 1 << 20, 1 << 12, dtype=np.uint64)
    his = los + (1 << 11)
    f_c, t_c = ctrl._touch_masks(los, his)
    f_h, t_h = hurt._touch_masks(los, his)
    np.testing.assert_array_equal(f_c, f_h)        # fences unaffected
    # the quarantined row may only ADD touches (fence-only pruning),
    # never drop one — that is the no-false-negative direction
    assert (t_h | t_c == t_h).all()
    assert hurt.scan_many(los, his) == ctrl.scan_many(los, his)
    assert hurt.stats.degraded_probes > 0
    assert ctrl.stats.degraded_probes == 0


def test_kernel_and_xla_quarantine_verdicts_match():
    rng = np.random.default_rng(11)
    snap = _filtered_store().snapshot()
    bad = _corrupt_one_filter(snap, rng)
    xla = Store.restore(copy.deepcopy(bad))
    ker = Store.restore(copy.deepcopy(bad))
    xla.cfg = dataclasses.replace(xla.cfg, scan_backend="xla")
    ker.cfg = dataclasses.replace(ker.cfg, scan_backend="kernel")
    los = np.arange(0, 1 << 20, 1 << 13, dtype=np.uint64)
    his = los + (1 << 12)
    f_x, t_x = xla._touch_masks(los, his)
    f_k, t_k = ker._touch_masks(los, his)
    np.testing.assert_array_equal(f_x, f_k)
    np.testing.assert_array_equal(t_x, t_k)


def test_scrub_quarantines_in_memory_bit_flip():
    import jax.numpy as jnp

    st = _filtered_store()
    run = next(r for r in st.live_runs() if r.state is not None)
    run.checksums()                   # build-time reference
    state = np.asarray(run.state).copy()
    state[len(state) // 2] ^= np.uint32(1 << 9)
    run.state = jnp.asarray(state)
    st._dirty = True
    report = st.scrub()
    assert report["newly_quarantined"] == 1
    assert run.quarantined
    assert report["fn_checked"] > 0   # and the no-FN assertion still held


def test_scrub_clean_store_reports_clean(tmp_path):
    st = Store(durable_config(tmp_path), _warn=False)
    for k in range(100):
        st.put(k, k)
    st.flush()
    report = st.scrub()
    assert report["quarantined"] == 0 and report["fn_checked"] > 0


# ---------------------------------------------------------------------------
# runtime kernel fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["auto", "kernel"])
def test_pallas_dispatch_failure_propagates(backend):
    """A kernel failure fails the scan in every mode: ``auto`` does not
    retry the batch through the XLA plane and hide the device."""
    st = _filtered_store(scan_backend=backend)
    st.faults = FaultPlan(fail_pallas=1)
    st._scan_kernel_mode = lambda: "kernel"       # force dispatch on CPU
    with pytest.raises(RuntimeError, match="pallas"):
        st.scan_many([0], [100])
    st.faults = FaultPlan(fail_pallas=1)          # the device-resident path
    with pytest.raises(RuntimeError, match="pallas"):
        st.scan_probe_device(np.zeros(1, np.uint32),
                             np.full(1, 100, np.uint32))


# ---------------------------------------------------------------------------
# snapshot semantics + malformed-input hardening
# ---------------------------------------------------------------------------

def test_snapshot_flushes_memtable_by_default():
    st = Store(StoreConfig(d=16, memtable_limit=1000), _warn=False)
    st.put(1, "unflushed")
    snap = st.snapshot()              # flush_first=True default
    assert Store.restore(snap).get(1) == "unflushed"


def test_snapshot_noflush_warns_without_wal():
    st = Store(StoreConfig(d=16, memtable_limit=1000), _warn=False)
    st.put(1, "unflushed")
    with pytest.warns(RuntimeWarning, match="unflushed"):
        snap = st.snapshot(flush_first=False)
    assert Store.restore(snap).get(1) is None     # documented loss


def test_snapshot_noflush_quiet_with_wal(tmp_path, recwarn):
    st = Store(durable_config(tmp_path, memtable_limit=1000), _warn=False)
    st.put(1, "walled")
    st.snapshot(flush_first=False)    # WAL covers the memtable: no warning
    assert not [w for w in recwarn.list
                if issubclass(w.category, RuntimeWarning)]


def _mutate_snapshot(snap, rng):
    """One random structured mutation; returns (mutated, description)."""
    snap = copy.deepcopy(snap)
    runs = [e for lvl in snap["levels"] for e in lvl]
    choice = int(rng.integers(0, 10))
    if choice == 0:
        snap["schema"] = "bloomrf-store/v99"
        return snap, "bad store schema"
    if choice == 1:
        snap["levels"] = {"not": "a list"}
        return snap, "levels not a list"
    if choice == 2:
        snap["config"] = {"filter_backend": "quantum"}
        return snap, "unknown backend"
    if choice == 3:
        snap["config"] = ["d", 16]
        return snap, "config not a dict"
    if not runs:
        snap["schema"] = None
        return snap, "no runs: bad schema"
    run = runs[rng.integers(0, len(runs))]
    if choice == 4:
        run["n"] = int(run["n"]) + 1
        return snap, "n mismatch"
    if choice == 5:
        ef = dict(run["keys"])
        plane = "low" if np.size(ef.get("low")) else "high"
        arr = np.array(ef[plane], np.uint8, copy=True)
        arr[rng.integers(0, arr.size)] ^= np.uint8(1 << rng.integers(0, 8))
        ef[plane] = arr
        run["keys"] = ef
        return snap, "key posting-list bit flip"
    if choice == 6 and run["vals"]:
        i = int(rng.integers(0, len(run["vals"])))
        run["vals"] = list(run["vals"])
        run["vals"][i] = "CORRUPTED"
        return snap, "value swapped"
    if choice == 7:
        t = np.array(run["tombs"], np.uint8, copy=True)
        if t.size:
            t[rng.integers(0, t.size)] ^= np.uint8(1 << rng.integers(0, 8))
            run["tombs"] = t
            return snap, "tombstone mask bit flip"
    if choice == 8:
        run["layout"] = {"bogus": True}
        return snap, "bad layout"
    if choice == 9 and "filter" in run:
        flipped = flip_filter_bits(run, rng)
        snap["levels"] = [[flipped if e is run else e for e in lvl]
                         for lvl in snap["levels"]]
        return snap, "filter bit flip (quarantine, not error)"
    run["schema"] = "bloomrf-run/v99"
    return snap, "bad run schema"


def test_mutated_snapshots_never_silently_misrestore():
    """Property test: every random snapshot mutation either raises an
    actionable ValueError or restores to a store whose read results are
    identical to the uncorrupted control (quarantine path)."""
    base = _filtered_store(seed=5)
    for k in range(0, 1 << 20, 1 << 13):
        base.delete(k)                # mix tombstones into the state
    snap = base.snapshot()
    ctrl = Store.restore(copy.deepcopy(snap))
    qs = np.asarray(sorted({int(r.keys[i]) for r in ctrl.live_runs()
                            for i in range(0, len(r.keys), 7)}), np.uint64)
    los = np.arange(0, 1 << 20, 1 << 14, dtype=np.uint64)
    his = los + (1 << 12)
    ctrl_gets = ctrl.get_many(qs)
    ctrl_scans = ctrl.scan_many(los, his)
    rng = np.random.default_rng(FUZZ_SEED)
    outcomes = {"raised": 0, "degraded": 0}
    for _ in range(60):
        mut, what = _mutate_snapshot(snap, rng)
        try:
            st = Store.restore(mut)
        except ValueError:
            outcomes["raised"] += 1
            continue
        # restored without error: results must match the control exactly
        # (only filter-block corruption may land here, as quarantine)
        assert st.get_many(qs) == ctrl_gets, what
        assert st.scan_many(los, his) == ctrl_scans, what
        outcomes["degraded"] += 1
    assert outcomes["raised"] > 0 and outcomes["degraded"] > 0, outcomes


def test_restore_rejects_non_dict_inputs():
    for junk in (None, 42, [], "snapshot", {"schema": "bloomrf-store/v3"}):
        with pytest.raises(ValueError):
            Store.restore(junk)
    with pytest.raises(ValueError):
        Run.unpack({"schema": "bloomrf-run/v3"})
    with pytest.raises(ValueError):
        Run.unpack([1, 2, 3])


# ---------------------------------------------------------------------------
# crash-recovery fuzz: interleave ops, crash, reopen, verify
# ---------------------------------------------------------------------------

def _fuzz_round(tmpdir, seed, n_ops, seam, countdown):
    """One armed fuzz run: returns True if the seam actually fired."""
    rng = np.random.default_rng(seed)
    cfg = durable_config(tmpdir, d=16, memtable_limit=24, level0_runs=2)
    plan = FaultPlan(seed=seed, crashes={seam: countdown})
    store = Store.open(str(tmpdir), cfg, faults=plan)
    model = {int(k): v for k, v in zip(
        *np.unique(np.asarray([], np.uint64), return_index=True))}
    # rebuild the model by replaying what the durable dir already holds
    model = {}
    crashed = False
    inflight = None                   # (kind, keys) of the op that crashed
    for _ in range(n_ops):
        kind = rng.choice(["put", "put", "put", "del", "delm", "ckpt"])
        try:
            if kind == "put":
                k, v = int(rng.integers(0, 1 << 16)), int(rng.integers(1e9))
                inflight = ("put", {k: v})
                store.put(k, v)
                model[k] = v
            elif kind == "del":
                k = int(rng.integers(0, 1 << 16))
                inflight = ("del", {k: None})
                store.delete(k)
                model.pop(k, None)
            elif kind == "delm":
                ks = [int(x) for x in rng.integers(0, 1 << 16, 5)]
                inflight = ("delm", {k: None for k in ks})
                store.delete_many(ks)
                for k in ks:
                    model.pop(k, None)
            else:
                inflight = ("ckpt", {})
                store.checkpoint()
            inflight = None
        except InjectedCrash:
            crashed = True
            break
    store.close()
    # a real process death may also tear the record being framed at crash
    # time: append garbage that replay must ignore
    wal_path = os.path.join(str(tmpdir), WAL_FILENAME)
    if crashed and os.path.exists(wal_path) and rng.random() < 0.5:
        with open(wal_path, "ab") as f:
            f.write(b"\x40\x00\x00\x00torn-in-flight-frame")
    rec = Store.open(str(tmpdir))
    # zero lost acked writes; the crashed op itself may be in either state
    allowed_either = inflight[1] if (crashed and inflight) else {}
    for k, v in model.items():
        got = rec.get(k)
        if k in allowed_either:
            assert got in (v, allowed_either[k]), (seam, k)
        else:
            assert got == v, (seam, k, got, v)
    # zero false negatives: every live model key must be readable AND a
    # scan over its neighbourhood must return it
    live = sorted(k for k in model if k not in allowed_either
                  and model[k] is not None)
    if live:
        pick = live[:: max(1, len(live) // 32)]
        lo = np.asarray(pick, np.uint64)
        scans = rec.scan_many(lo, lo)
        for k, rows in zip(pick, scans):
            assert rows == [(k, model[k])], (seam, k)
    rec.scrub(sample_keys=16)
    rec.close()
    return crashed


@pytest.mark.parametrize("seam", CRASH_SEAMS)
def test_crash_recovery_fuzz_smoke(tmp_path, seam):
    fired = False
    for countdown in (1, 3, 9):
        sub = tmp_path / f"{seam.replace('.', '_')}-{countdown}"
        sub.mkdir()
        fired |= _fuzz_round(sub, FUZZ_SEED + countdown, 400, seam,
                             countdown)
    assert fired, f"seam {seam} never fired — dead injection point"


@pytest.mark.slow
def test_crash_recovery_fuzz_slow(tmp_path):
    """The 1e5-op soak: repeated crash/reopen cycles against one durable
    directory, cycling through every seam."""
    rng = np.random.default_rng(FUZZ_SEED)
    cfg = durable_config(tmp_path, d=16, memtable_limit=64, level0_runs=2)
    model, ops_done, crashes = {}, 0, 0
    seam_i = 0
    store = Store.open(str(tmp_path), cfg)
    while ops_done < 100_000:
        if store.faults is None or not any(
                store.faults.armed(s) for s in CRASH_SEAMS):
            seam = CRASH_SEAMS[seam_i % len(CRASH_SEAMS)]
            seam_i += 1
            store.faults = FaultPlan(seed=int(rng.integers(1 << 30)),
                                     crashes={seam: int(rng.integers(1, 40))})
        kind = rng.choice(["put", "put", "put", "del", "ckpt"],
                          p=[0.3, 0.3, 0.3, 0.09, 0.01])
        inflight = None
        try:
            if kind == "put":
                k, v = int(rng.integers(0, 1 << 16)), ops_done
                inflight = (k, v)
                store.put(k, v)
                model[k] = v
            elif kind == "del":
                k = int(rng.integers(0, 1 << 16))
                inflight = (k, None)
                store.delete(k)
                model.pop(k, None)
            else:
                store.checkpoint()
        except InjectedCrash:
            crashes += 1
            store.close()
            store = Store.open(str(tmp_path))
            if inflight is not None:
                k, v = inflight
                got = store.get(k)
                assert got in (v, model.get(k)), (k, got)
                # pin the model to whatever the store durably decided
                if got is None:
                    model.pop(k, None)
                else:
                    model[k] = got
        ops_done += 1
    assert crashes >= 10, crashes
    store.close()
    rec = Store.open(str(tmp_path))
    keys = np.asarray(sorted(model), np.uint64)
    got = rec.get_many(keys)
    assert got == [model[int(k)] for k in keys]
    rec.scrub()


# ---------------------------------------------------------------------------
# serve: cold tier reopens through recovery
# ---------------------------------------------------------------------------

def test_prefix_cache_cold_tier_recovers(tmp_path):
    from repro.serve.prefix_cache import PrefixCacheIndex, pack_key

    cfg = StoreConfig(d=32, memtable_limit=64, durability="wal",
                      wal_dir=str(tmp_path))
    idx = PrefixCacheIndex(n_tenants=4,
                           backing_store=Store(cfg, _warn=False))
    idx.freeze_segment({pack_key(s, c): [s * 100 + c]
                        for s in range(8) for c in range(4)})
    idx.evict_window(6, 7)            # tombstones must survive recovery too
    idx.store.close()                 # crash before any checkpoint

    idx2 = PrefixCacheIndex(n_tenants=4)
    store = idx2.reopen_cold_tier(str(tmp_path))
    assert store.stats.wal_replayed > 0
    # no segments in the fresh index: lookups fall through to the cold tier
    assert idx2.lookup(3, 2) == [302]
    assert idx2.lookup(6, 1) is None  # evicted stays evicted
    assert idx2.stats["store_hits"] == 1


# ---------------------------------------------------------------------------
# Supervisor backoff (reusing the fault harness for injected failures)
# ---------------------------------------------------------------------------

class _FlakyTrainer:
    """Trainer stub whose run() crashes through a FaultPlan seam."""

    straggler_events: list = []
    start_step = 0

    def __init__(self, plan):
        self.plan = plan

    def run(self):
        self.plan.hit("trainer.step")
        return {"ok": True}


def test_supervisor_backoff_schedule_and_budget():
    from repro.train.fault_tolerance import Supervisor

    sleeps = []
    plan = FaultPlan(seed=1, crashes={"trainer.step": 1})

    def factory():
        # re-arm every attempt: the trainer never recovers
        plan._remaining["trainer.step"] = 1
        return _FlakyTrainer(plan)

    sup = Supervisor(factory, max_restarts=3, backoff_base=1.0,
                     backoff_cap=4.0, jitter=0.5, seed=7,
                     sleep=sleeps.append)
    with pytest.raises(RuntimeError, match="exceeded 3 restarts"):
        sup.run()
    assert len(sup.incidents) == 4    # budget + the final fatal attempt
    assert len(sleeps) == 3           # no sleep after the fatal one
    bases = [1.0, 2.0, 4.0]           # doubling, capped at 4.0
    for s, b in zip(sleeps, bases):
        assert b <= s <= b * 1.5, (s, b)
    assert [i["backoff_s"] for i in sup.incidents][:3] == sleeps


def test_supervisor_successful_recovery_resets_budget():
    from repro.train.fault_tolerance import Supervisor

    sleeps = []
    attempts = []

    def factory():
        # arm a fresh one-shot crash for the first two attempts only
        attempts.append(1)
        crashes = {"trainer.step": 1} if len(attempts) <= 2 else {}
        return _FlakyTrainer(FaultPlan(seed=2, crashes=crashes))

    sup = Supervisor(factory, max_restarts=2,
                     backoff_base=0.25, jitter=0.0, seed=0,
                     sleep=sleeps.append)
    out = sup.run()                   # crashes twice, then succeeds
    assert out["metrics"] == {"ok": True} and out["restarts"] == 2
    assert sleeps == [0.25, 0.5]
    # a fresh run() starts with a full budget (consecutive-failure reset):
    # one more crash would blow a carried-over budget of 2, but passes here
    plan2 = FaultPlan(seed=3, crashes={"trainer.step": 1})
    sup.factory = lambda: _FlakyTrainer(plan2)
    out2 = sup.run()
    assert out2["metrics"] == {"ok": True} and out2["restarts"] == 1


def test_supervisor_rejects_bad_backoff():
    from repro.train.fault_tolerance import Supervisor

    with pytest.raises(ValueError):
        Supervisor(lambda: None, jitter=2.0)


# ---------------------------------------------------------------------------
# snapshot files are real bytes (pickle) end to end
# ---------------------------------------------------------------------------

def test_snapshot_file_crc_detects_rot(tmp_path):
    st = Store(durable_config(tmp_path), _warn=False)
    for k in range(200):
        st.put(k, k)
    path = st.checkpoint()
    st.close()
    with open(path, "r+b") as f:      # rot one byte mid-file
        f.seek(os.path.getsize(path) // 3)
        c = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([c[0] ^ 0x10]))
    with pytest.raises(ValueError, match="CRC"):
        Store.open(str(tmp_path))


def test_run_pack_v3_carries_checksums():
    st = _filtered_store()
    run = st.live_runs()[0]
    enc = run.pack()
    assert enc["schema"] == "bloomrf-run/v3"
    assert set(enc["crc"]) >= {"keys", "fences", "vals", "tombs"}
    if run.state is not None:
        assert "filter" in enc["crc"]
    back = Run.unpack(pickle.loads(pickle.dumps(enc)))
    np.testing.assert_array_equal(back.keys, run.keys)
    assert not back.quarantined
