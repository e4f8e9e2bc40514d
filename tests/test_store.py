"""LSM run-store (src/repro/store): correctness of the read/write path,
compaction filter merging, the one-gather stacked probe invariant, EF run
snapshots, and the store-level pruning acceptance vs the fence baseline.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import basic_layout
from repro.store import Run, Store, StoreConfig, merge_sorted_runs, scan_plane

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _count_gathers(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                n += _count_gathers(v.jaxpr)
            elif isinstance(v, (list, tuple)):
                n += sum(_count_gathers(it.jaxpr) for it in v
                         if hasattr(it, "jaxpr"))
    return n


# ---------------------------------------------------------------------------
# basic read/write semantics
# ---------------------------------------------------------------------------

def test_put_get_delete_through_flushes():
    st = Store(StoreConfig(d=32, memtable_limit=50, level0_runs=3))
    for k in range(300):
        st.put(k * 7, k)
    assert st.n_runs >= 1
    assert st.get(7 * 7) == 7
    assert st.get(7 * 7 + 1) is None
    # delete a flushed key: tombstone masks the older run
    st.delete(7 * 7)
    assert st.get(7 * 7) is None
    st.flush()                        # tombstone now lives in a run
    assert st.get(7 * 7) is None
    # overwrite: newest occurrence wins
    st.put(7 * 14, -1)
    assert st.get(7 * 14) == -1


def test_scan_merges_levels_and_masks_tombstones():
    st = Store(StoreConfig(d=32, memtable_limit=40, level0_runs=2))
    for k in range(0, 400, 2):
        st.put(k, k)
    for k in range(0, 100, 4):        # delete every other stored key < 100
        st.delete(k)
    st.put(13, 1313)                  # odd key only in the memtable
    got = st.scan(0, 99)
    want = sorted([(k, k) for k in range(0, 100, 2) if k % 4 != 0]
                  + [(13, 1313)])
    assert got == want


def test_scan_bounds_beyond_domain_clamp_not_wrap():
    """A scan hi past 2^d must clamp for the filter probe, not wrap under
    the kdtype cast (wrapping swaps the normalised interval and produced
    filter false negatives the fences don't catch)."""
    st = Store(StoreConfig(d=32, memtable_limit=100, level0_runs=3))
    for k in range(0, 4000):
        st.put(k * 1_000_000, k)         # keys up to ~4.0e9, near 2^32
    st.flush()
    got = st.scan(100, (1 << 32) + 50)   # hi would wrap to 50
    assert len(got) == 3999              # every key except 0
    assert st.scan(1 << 33, (1 << 34)) == []   # entirely above the domain
    # out-of-domain point lookups answer None (fenced off), never alias
    assert st.get_many(np.asarray([1 << 33], np.uint64)) == [None]


def test_rejects_out_of_domain_keys():
    st = Store(StoreConfig(d=16))
    with pytest.raises(ValueError, match="outside"):
        st.put(1 << 16, 0)
    with pytest.raises(ValueError):
        StoreConfig(fanout=1)
    with pytest.raises(ValueError, match="filter_backend"):
        StoreConfig(filter_backend="nope")


# ---------------------------------------------------------------------------
# compaction: both filter-merge paths, entry merge precedence
# ---------------------------------------------------------------------------

def test_compaction_exercises_or_and_rebuild_merges(rng):
    st = Store(StoreConfig(d=32, memtable_limit=200, level0_runs=2,
                           fanout=4))
    keys = rng.integers(0, 1 << 32, 3000, dtype=np.uint64)
    for i, k in enumerate(keys):
        st.put(int(k), i)
    st.flush()
    assert st.stats.compactions > 0
    # class-graduating merges re-insert; same-class merges bitwise-OR
    assert st.stats.rebuild_merges > 0
    assert st.stats.or_merges > 0
    model = {int(k): i for i, k in enumerate(keys)}
    got = st.get_many(keys[:500])
    assert got == [model[int(k)] for k in keys[:500]]


def test_merge_sorted_runs_newest_wins_and_drops_tombstones():
    lay = basic_layout(32, 10, 8.0, delta=4)
    new = Run(np.asarray([5, 10], np.uint64), ["n5", "n10"],
              np.asarray([False, True]), 0, lay, None)
    old = Run(np.asarray([5, 7, 10], np.uint64), ["o5", "o7", "o10"],
              np.asarray([False, False, False]), 1, lay, None)
    keys, vals, tombs = merge_sorted_runs([new, old])
    assert list(keys) == [5, 7, 10] and vals == ["n5", "o7", "n10"]
    assert list(tombs) == [False, False, True]
    keys, vals, tombs = merge_sorted_runs([new, old], drop_tombstones=True)
    assert list(keys) == [5, 7] and vals == ["n5", "o7"]


def test_compaction_preserves_deletes_across_levels():
    st = Store(StoreConfig(d=32, memtable_limit=30, level0_runs=2))
    for k in range(600):
        st.put(k, k)
    for k in range(0, 600, 3):
        st.delete(k)
    st.flush()
    while len(st.levels[0]) or sum(bool(lv) for lv in st.levels) > 1:
        lvl = next(lv for lv in range(len(st.levels)) if st.levels[lv])
        st.compact(lvl)               # force everything into one bottom run
        if st.n_runs <= 1:
            break
    for k in range(0, 60, 3):
        assert st.get(k) is None
    for k in range(1, 60, 3):
        assert st.get(k) == k
    # bottom-level merge garbage-collected the tombstones
    bottom = st.live_runs()[0]
    assert not bottom.tombs.any()


# ---------------------------------------------------------------------------
# the one-gather invariant over >= 8 live runs of mixed capacity classes
# ---------------------------------------------------------------------------

def _store_with_runs(rng, min_runs=9):
    st = Store(StoreConfig(d=32, memtable_limit=100, level0_runs=8,
                           fanout=4))
    i = 0
    while st.n_runs < min_runs:
        for _ in range(100):
            st.put(int(rng.integers(0, 1 << 32)), i)
            i += 1
        st.flush()
    return st


def test_scan_over_8_runs_is_one_gather(rng):
    st = _store_with_runs(rng, 9)
    runs = st.live_runs()
    assert len(runs) >= 8
    assert len({r.layout for r in runs}) >= 2   # mixed capacity classes
    lo = jnp.zeros(64, jnp.uint32)
    hi = jnp.full(64, 1 << 20, jnp.uint32)
    stack = st._run_stack()
    jaxpr = jax.make_jaxpr(scan_plane.range_all)(stack, lo, hi)
    assert _count_gathers(jaxpr.jaxpr) == 1, jaxpr.pretty_print()
    jaxpr_p = jax.make_jaxpr(scan_plane.point_all)(stack, lo)
    assert _count_gathers(jaxpr_p.jaxpr) == 1
    jaxpr_t = jax.make_jaxpr(scan_plane.touch_all)(stack, lo, hi)
    assert _count_gathers(jaxpr_t.jaxpr) == 1


def test_stacked_probe_matches_per_run_probes(rng):
    st = _store_with_runs(rng, 9)
    runs = st.live_runs()
    lo = rng.integers(0, 1 << 32, 2000, dtype=np.uint64)
    hi = np.minimum(lo + (1 << 14), (1 << 32) - 1)
    _, filt = st.probe_runs(lo, hi)
    from repro.core.engine import _filter_for_layout
    for j, r in enumerate(runs):
        f = _filter_for_layout(r.layout)
        want = np.asarray(f.range(r.state, jnp.asarray(lo, jnp.uint32),
                                  jnp.asarray(hi, jnp.uint32)))
        np.testing.assert_array_equal(filt[:, j], want)


# ---------------------------------------------------------------------------
# acceptance fuzz: 1e5 mixed ops, scans + final sweep never miss a live key
# ---------------------------------------------------------------------------

def test_fuzz_100k_ops_never_misses_a_stored_key():
    rng = np.random.default_rng(0xF022)
    st = Store(StoreConfig(d=32, memtable_limit=2000, level0_runs=4,
                           fanout=4))
    model = {}
    N_OPS = 100_000
    CHUNK = 2_000
    SCAN_B = 64                       # fixed probe batch (one compile per R)
    n_scans = 0
    for c0 in range(0, N_OPS, CHUNK):
        ops = rng.random(CHUNK)
        ks = rng.integers(0, 1 << 32, CHUNK, dtype=np.uint64)
        for op, k in zip(ops, ks):
            k = int(k)
            if op < 0.92:
                st.put(k, k ^ 0xABCD)
                model[k] = k ^ 0xABCD
            else:
                dk = int(ks[rng.integers(0, CHUNK)])
                st.delete(dk)
                model.pop(dk, None)
        # the chunk's scans, batched (and padded) to one fused probe
        lo = rng.integers(0, (1 << 32) - (1 << 16), SCAN_B, dtype=np.uint64)
        hi = lo + rng.integers(1, 1 << 16, SCAN_B, dtype=np.uint64)
        results = st.scan_many(lo, hi)
        n_scans += SCAN_B
        sorted_keys = np.sort(np.fromiter(model.keys(), np.uint64,
                                          len(model)))
        for ql, qh, res in zip(lo, hi, results):
            a, b = np.searchsorted(sorted_keys, [ql, qh + 1])
            want = [(int(k), model[int(k)]) for k in sorted_keys[a:b]]
            assert res == want, (ql, qh, len(res), len(want))
    assert st.stats.compactions > 0 and st.stats.flushes > 10
    assert n_scans + st.stats.puts + st.stats.deletes >= N_OPS
    # final sweep: every live key, batched point lookups
    live = np.fromiter(model.keys(), np.uint64, len(model))
    got = st.get_many(live)
    misses = sum(g != model[int(k)] for g, k in zip(got, live))
    assert misses == 0, f"{misses}/{len(live)} stored keys missed"


# ---------------------------------------------------------------------------
# pruning acceptance at store level: filters beat fences by >= 2x
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["uniform", "zipf"])
def test_filter_pruning_beats_fences_by_2x(dist):
    rng = np.random.default_rng(0xACCE)
    if dist == "uniform":
        keys = rng.integers(0, 1 << 31, 8000, dtype=np.uint64)
    else:
        z = rng.zipf(1.2, 8000).astype(np.float64)
        z = z / (z.max() + 1.0)
        keys = ((z * float(1 << 31)).astype(np.uint64)
                + rng.integers(0, 1 << 22, 8000, dtype=np.uint64))
    lo = rng.integers(0, 1 << 31, 3000, dtype=np.uint64)
    hi = lo + 255
    probed = {}
    for backend in ("bloomrf", "none"):
        st = Store(StoreConfig(d=32, memtable_limit=500, level0_runs=8,
                               filter_backend=backend))
        for i, k in enumerate(keys):
            st.put(int(k), i)
        st.flush()
        assert st.n_runs >= 8
        st.scan_many(lo, hi)
        probed[backend] = st.stats.runs_probed_per_scan
    assert probed["bloomrf"] <= 0.5 * probed["none"], probed


# ---------------------------------------------------------------------------
# Elias-Fano run snapshots (dist/compression.py) round-trip bit-exactly
# ---------------------------------------------------------------------------

def test_run_snapshot_roundtrip(rng):
    st = Store(StoreConfig(d=32, memtable_limit=300, level0_runs=3))
    keys = rng.integers(0, 1 << 32, 2500, dtype=np.uint64)
    for i, k in enumerate(keys):
        st.put(int(k), i)
    st.delete(int(keys[0]))
    st.flush()
    for run in st.live_runs():
        enc = run.pack()
        back = Run.unpack(enc)
        np.testing.assert_array_equal(back.keys, run.keys)
        np.testing.assert_array_equal(back.tombs, run.tombs)
        assert back.vals == run.vals and back.layout == run.layout
        np.testing.assert_array_equal(np.asarray(back.state),
                                      np.asarray(run.state))
    # store-level snapshot: restored store answers identically
    snap = st.snapshot()
    st2 = Store.restore(snap)
    qs = rng.integers(0, 1 << 32, 1000, dtype=np.uint64)
    assert st2.get_many(np.concatenate([keys[:500], qs])) == \
        st.get_many(np.concatenate([keys[:500], qs]))
    lo = rng.integers(0, 1 << 32, 200, dtype=np.uint64)
    hi = np.minimum(lo + (1 << 12), (1 << 32) - 1)
    assert st2.scan_many(lo, hi) == st.scan_many(lo, hi)


def test_snapshot_beats_raw_dump_when_sparse(rng):
    st = Store(StoreConfig(d=32, memtable_limit=400, level0_runs=4,
                           bits_per_key=24.0))
    for k in rng.integers(0, 1 << 32, 400, dtype=np.uint64):
        st.put(int(k), 0)
    st.flush()
    run = st.live_runs()[0]
    enc = run.pack()["filter"]
    from repro.dist.compression import elias_fano_size_bits
    assert elias_fano_size_bits(enc) < run.layout.total_bits


# ---------------------------------------------------------------------------
# kernel-path filter builds (use_insert_kernels) agree with the XLA path
# ---------------------------------------------------------------------------

def test_kernel_insert_path_builds_identical_filters(rng):
    keys = rng.integers(0, 1 << 32, 1500, dtype=np.uint64)
    states = []
    for use_kernels in (False, True):
        st = Store(StoreConfig(d=32, memtable_limit=1500,
                               use_insert_kernels=use_kernels))
        for i, k in enumerate(keys):
            st.put(int(k), i)
        st.flush()
        states.append(np.asarray(st.live_runs()[0].state))
    np.testing.assert_array_equal(states[0], states[1])


# ---------------------------------------------------------------------------
# nightly YCSB-E row (slow): the benchmark acceptance at larger sizes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ycsb_e_row_slow():
    from benchmarks import store_bench as sb
    saved = {a: getattr(sb, a) for a in
             ("N", "OPS", "MEMTABLE", "SCAN_BATCH")}
    try:
        sb.N, sb.OPS, sb.MEMTABLE, sb.SCAN_BATCH = 60_000, 6_000, 2_000, 512
        for dist in ("uniform", "zipf"):
            rf, _, _ = sb.run_one("bloomrf", dist)
            mm, _, _ = sb.run_one("none", dist)
            r, m = (rf.stats.runs_probed_per_scan,
                    mm.stats.runs_probed_per_scan)
            assert r <= 0.5 * m, (dist, r, m)
    finally:
        for a, v in saved.items():
            setattr(sb, a, v)


def test_store_stats_dict_shape():
    s = Store(StoreConfig(d=16)).stats
    d = s.as_dict()
    assert {"runs_probed_per_scan", "scan_fp_read_rate",
            "get_fp_read_rate"} <= set(d)
    assert dataclasses.is_dataclass(s)


# ---------------------------------------------------------------------------
# config validation (d / bits_per_key / mutability boundaries)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(d=0), dict(d=65), dict(d=-3),
    dict(d=24, bits_per_key=0.0), dict(d=24, bits_per_key=-2.0),
    dict(d=24, mutability="append_only"),
    dict(d=24, mutability="deletable", purge_dead_frac=0.0),
    dict(d=24, mutability="deletable", purge_dead_frac=1.5),
])
def test_store_config_rejects_bad_values(bad):
    with pytest.raises(ValueError):
        StoreConfig(**bad)


@pytest.mark.parametrize("d", [1, 64])
def test_store_config_domain_boundaries_work(d):
    """d=1 and d=64 are legal domains: keys round-trip through flushes."""
    st = Store(StoreConfig(d=d, memtable_limit=4, level0_runs=2))
    keys = [0, 1] if d == 1 else [0, 1, 12345, (1 << 64) - 1]
    for i, k in enumerate(keys):
        st.put(k, i)
    st.flush()
    assert st.get_many(np.asarray(keys, np.uint64)) == list(range(len(keys)))
    assert st.get(0) == 0


# ---------------------------------------------------------------------------
# bytes accounting: read + not-read must cover every considered run
# ---------------------------------------------------------------------------

def _runs_only_store(rng, n=800):
    st = Store(StoreConfig(d=24, memtable_limit=128, level0_runs=4,
                           bits_per_key=12.0))
    keys = np.unique(rng.integers(0, 1 << 23, n).astype(np.uint64))
    for i, k in enumerate(keys):
        st.put(int(k), i)
    st.flush()                          # no memtable residue
    assert len(st.live_runs()) >= 2
    return st, keys


def test_bytes_accounting_is_conserved_on_gets(rng):
    """Point path: every run is either read or credited to bytes_not_read —
    the counters partition the considered data bytes (regression: the get
    path used to never credit skipped runs, understating filter savings)."""
    st, _ = _runs_only_store(rng)
    total = sum(r.data_bytes(st.cfg.value_bytes) for r in st.live_runs())
    absent = np.arange(1 << 23, (1 << 23) + 500, dtype=np.uint64)
    r0, n0 = st.stats.bytes_read, st.stats.bytes_not_read
    st.get_many(absent)
    dr = st.stats.bytes_read - r0
    dn = st.stats.bytes_not_read - n0
    assert dr + dn == len(absent) * total
    assert dn > 0, "no filter/fence credit on the point path"


def test_bytes_accounting_is_conserved_on_scans(rng):
    st, _ = _runs_only_store(rng)
    total = sum(r.data_bytes(st.cfg.value_bytes) for r in st.live_runs())
    lo = np.arange(1 << 23, (1 << 23) + 300, dtype=np.uint64)
    r0, n0 = st.stats.bytes_read, st.stats.bytes_not_read
    st.scan_many(lo, lo + 3)
    dr = st.stats.bytes_read - r0
    dn = st.stats.bytes_not_read - n0
    assert dr + dn == len(lo) * total
    assert dn > 0


# ---------------------------------------------------------------------------
# batched deletes flush at most once per call
# ---------------------------------------------------------------------------

def test_delete_many_flushes_at_most_once():
    st = Store(StoreConfig(d=24, memtable_limit=128, level0_runs=8))
    for k in range(1000, 1600):
        st.put(k, k)
    st.flush()
    f0 = st.stats.flushes
    st.delete_many(range(1000, 1500))   # 500 tombstones >> memtable_limit
    assert st.stats.flushes - f0 <= 1
    assert st.stats.deletes >= 500
    st.flush()
    assert all(v is None for v in st.get_many(
        np.arange(1000, 1500, dtype=np.uint64)))
    assert st.get_many(np.arange(1500, 1600, dtype=np.uint64)) == \
        list(range(1500, 1600))
