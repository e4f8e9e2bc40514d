"""The store's scan plane (``store/scan_plane.py``): layouts as data over a
padded stack.

The plane must answer bit for bit what ``StackedProbe`` answers for the
same rows (range, point, and the fence-fused touch), with padded rows
answering False, and a store that flushes and compacts inside the
plane's shape must build no program at all.
"""
import bisect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FilterLayout, basic_layout
from repro.core.engine import _filter_for_layout, stacked_probe
from repro.store import Store, StoreConfig, scan_plane


def _ladder(d, cls, delta):
    return basic_layout(d, 4096 * 4 ** cls, 14.0, delta=delta)


U64 = [_ladder(64, c, 7) for c in range(5)]    # k = 8, 8, 7, 7, 7
U32 = [_ladder(32, c, 6) for c in range(3)]
SEG = FilterLayout(d=32, deltas=(6, 5, 4), replicas=(1, 1, 1),
                   seg_of_layer=(0, 1, 0), seg_bits=(8192, 4096))
REP = FilterLayout(d=32, deltas=(7, 7), replicas=(1, 2),
                   seg_of_layer=(0, 0), seg_bits=(16384,))

# (layouts, quarantined row or None); every stack pads to one shape of
# 8 rows, so the plane compiles once per key width
STACKS = {
    "u64_one_row": ([U64[0]], None),
    "u64_ladder": ([U64[0], U64[0], U64[2], U64[3], U64[4]], 1),
    "u64_deep_first": ([U64[3], U64[0], U64[1]], None),
    "u64_full": ([U64[0]] * 4 + U64[1:], 6),
    "u32_ladder": ([U32[0], U32[0], U32[1], U32[2]], 3),
    "u32_mixed_delta": ([basic_layout(32, 500, 12.0, delta=dl)
                         for dl in (4, 6, 7)], None),
    "u32_segments_replicas": ([SEG, REP, U32[0], REP], 0),
}


def _rows(layouts, rng):
    d = layouts[0].d
    kd = jnp.uint64 if d > 32 else jnp.uint32
    keys, states = [], []
    for lay in layouts:
        k = rng.integers(0, 1 << d, 600, dtype=np.uint64)
        keys.append(k)
        f = _filter_for_layout(lay)
        states.append(f.build(jnp.asarray(k, kd)))
    return keys, states, kd


def _queries(keys, d, rng, b=2048):
    dmax = (1 << d) - 1
    lo = rng.integers(0, 1 << d, b, dtype=np.uint64)
    width = rng.integers(0, 1 << min(d - 1, 40), b, dtype=np.uint64)
    width >>= rng.integers(0, min(d - 1, 40), b, dtype=np.uint64)
    # a quarter of the ranges end just past a stored key: true positives
    held = np.concatenate(keys)[rng.integers(0, sum(map(len, keys)), b // 4)]
    lo[: b // 4] = held - np.minimum(held, width[: b // 4])
    hi = np.minimum(lo + width, dmax)
    hi[: b // 4] = np.maximum(hi[: b // 4], held)
    lo[:4], hi[:4] = 0, dmax                # the whole domain
    return lo, hi, held


@pytest.mark.parametrize("case", sorted(STACKS))
def test_plane_matches_stacked_probe(case, rng):
    layouts, quar_row = STACKS[case]
    keys, states, kd = _rows(layouts, rng)
    n = len(layouts)
    kmin = np.asarray([k.min() for k in keys], np.uint64)
    kmax = np.asarray([k.max() for k in keys], np.uint64)
    quar = np.zeros(n, bool)
    if quar_row is not None:
        quar[quar_row] = True
    shape = scan_plane.PlaneShape.of(layouts).cover(
        scan_plane.PlaneShape(8, 1 << 20, 8, 2))
    assert shape == (8, 1 << 20, 8, 2)
    stack = scan_plane.stack_runs(layouts, states, kmin, kmax, quar, shape)
    bases = tuple(int(b) for b in np.cumsum(
        [0] + [lay.total_u32 for lay in layouts[:-1]]))
    ref = stacked_probe(tuple(layouts), bases)
    flat = jnp.concatenate(states)

    lo, hi, held = _queries(keys, layouts[0].d, rng)
    lo_j, hi_j = jnp.asarray(lo, kd), jnp.asarray(hi, kd)
    want = np.asarray(ref.range_all(flat, lo_j, hi_j))
    got = np.asarray(scan_plane.range_all(stack, lo_j, hi_j))
    np.testing.assert_array_equal(got[:, :n], want)
    assert not got[:, n:].any()            # padded rows answer False
    assert want.any() and not want.all()   # the verdicts discriminate

    pts = np.concatenate([held, lo[len(held):]])    # stored keys first
    pts_j = jnp.asarray(pts, kd)
    want_p = np.asarray(ref.point_all(flat, pts_j))
    got_p = np.asarray(scan_plane.point_all(stack, pts_j))
    np.testing.assert_array_equal(got_p[:, :n], want_p)
    assert not got_p[:, n:].any()

    fence_w, touch_w = ref.touch_all(
        flat, jnp.asarray(kmin, kd), jnp.asarray(kmax, kd), lo_j, hi_j,
        jnp.asarray(quar) if quar.any() else None)
    fence, touch = (np.asarray(a)
                    for a in scan_plane.touch_all(stack, lo_j, hi_j))
    np.testing.assert_array_equal(fence[:, :n], np.asarray(fence_w))
    np.testing.assert_array_equal(touch[:, :n], np.asarray(touch_w))
    assert not fence[:, n:].any() and not touch[:, n:].any()
    if quar_row is not None:
        # a quarantined row is pruned by its fence alone, though its filter
        # would have ruled some of those ranges out
        np.testing.assert_array_equal(touch[:, quar_row], fence[:, quar_row])
        assert (fence[:, quar_row] & ~want[:, quar_row]).any()


def test_stack_runs_rejects_what_the_plane_cannot_hold():
    shape = scan_plane.PlaneShape.of(U32[:2]).cover(
        scan_plane.PlaneShape(2, 0, 0, 1))
    with pytest.raises(ValueError, match="does not fit"):
        scan_plane.stack_runs(U32, [None] * 3, [0] * 3, [0] * 3,
                              [False] * 3, shape)
    exact = FilterLayout(d=16, deltas=(4,), replicas=(1,), seg_of_layer=(0,),
                         seg_bits=(256, 1 << 12), exact_seg=1)
    with pytest.raises(ValueError, match="exact-bitmap"):
        scan_plane.stack_runs([exact], [None], [0], [0], [False],
                              scan_plane.PlaneShape.of([exact]))
    with pytest.raises(ValueError, match="one key domain"):
        scan_plane.stack_runs([U32[0], U64[0]], [None] * 2, [0] * 2,
                              [0] * 2, [False] * 2,
                              scan_plane.PlaneShape.of([U32[0], U64[0]]))


# ---------------------------------------------------------------------------
# a store that flushes and compacts inside the plane's shape builds nothing
# ---------------------------------------------------------------------------

class _Builds:
    """Programs JAX builds while the block runs (backend-compile events)."""

    def __enter__(self):
        from jax._src import dispatch

        self.n = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, name, secs, **_):
        self.n += name == self._event

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def test_store_scans_build_no_program_across_flushes_and_compactions():
    rng = np.random.default_rng(0x5CA9)
    st = Store(StoreConfig(d=64, delta=7, memtable_limit=4096, fanout=4,
                           level0_runs=4, scan_backend="xla"), _warn=False)
    ref = {}
    pending = iter(rng.integers(0, 1 << 64, 200_000, dtype=np.uint64)
                   .tolist())

    def flush_once():
        flushes = st.stats.flushes
        while st.stats.flushes == flushes:
            k = next(pending)
            st.put(k, k ^ 0x5A)
            ref[k] = k ^ 0x5A

    def scan_and_check(b):
        keys = sorted(ref)
        lo = np.asarray(keys, np.uint64)[rng.integers(0, len(keys), b)]
        hi = lo + rng.integers(0, 1 << 52, b, dtype=np.uint64)
        hi = np.maximum(hi, lo)                 # no wrap past 2^64
        for a, z, got in zip(lo.tolist(), hi.tolist(),
                             st.scan_many(lo, hi)):
            i, j = bisect.bisect_left(keys, a), bisect.bisect_right(keys, z)
            assert got == [(k, ref[k]) for k in keys[i:j]]

    # two L0 -> L1 -> L2 cycles meet every filter-build and merge shape
    # the next cycle needs; the scans then build the plane at 60 and 61
    for _ in range(10):
        flush_once()
    assert [len(lv) for lv in st.levels] == [0, 0, 1]
    for b in (60, 61):
        scan_and_check(b)
    assert {r.layout.k for r in st.live_runs()} == {7}
    st.stats.reset()

    mixed = False
    with _Builds() as builds:
        for i in range(5):
            flush_once()
            # class-0 runs (k = 8) on the class-2 run (k = 7)
            mixed |= {r.layout.k for r in st.live_runs()} == {7, 8}
            scan_and_check(60 + i % 2)
    assert builds.n == 0
    assert st.stats.compactions == 2            # L0 -> L1, then L1 -> L2
    assert len(st.levels) == 3
    assert mixed
    assert st.stats.scan_plane_restacks == 5    # one stack per refresh
    assert st.stats.scan_plane_grows == 0

    # a fourth cycle adds level 3: the lane bucket grows, once
    while len(st.levels) == 3:
        flush_once()
    scan_and_check(60)
    scan_and_check(61)
    assert st.stats.scan_plane_grows == 1
