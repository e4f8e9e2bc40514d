"""Property tests of the compaction filter-merge invariant (DESIGN.md §10):
a merged filter state admits no false negatives vs a bulk rebuild over the
union of the source runs' keys — across mixed Δ layouts, multi-segment
layouts, replicas, and tombstone-dropping merges.

The hypothesis suite explores the space; ``test_merge_invariant_seeded``
repeats the core check on seeded draws so the invariant stays exercised
even where hypothesis is not installed (it is CI-installed but optional
locally, matching test_bloomrf_property.py).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BloomRF, FilterLayout, basic_layout
from repro.store import Store, StoreConfig
from repro.store.compaction import merge_filter_state
from repro.store.run import Run

try:
    from hypothesis import given, settings, strategies as hst
except ImportError:                                     # pragma: no cover
    hst = None


def _pow2(keys):
    """Pad a key batch to a power-of-two length by repeating its first key.

    A repeated key sets no new bit and probes exactly as it did, so every
    check is unchanged; the batch shapes JAX compiles for stay few."""
    keys = np.asarray(keys)
    n = len(keys)
    return np.concatenate([keys, np.repeat(keys[:1],
                                           (1 << (n - 1).bit_length()) - n)])


def _check_union_no_fn(layout, state, union_keys):
    """Every union key (and every straddling range) probes positive."""
    f = BloomRF(layout)
    union_keys = _pow2(union_keys)
    kj = jnp.asarray(union_keys, f.kdtype)
    assert np.asarray(f.point(state, kj)).all()
    ks = np.asarray(union_keys, np.uint64)
    lo = np.maximum(ks, 2) - 2
    hi = np.minimum(ks + 3, (1 << layout.d) - 1)
    assert np.asarray(f.range(state, jnp.asarray(lo, f.kdtype),
                              jnp.asarray(hi, f.kdtype))).all()


def _merge_case(layout_a, layout_b, target, keys_a, keys_b):
    """Merge two runs' filters under ``target``; verify vs bulk rebuild."""
    def build(lay, keys):
        f = BloomRF(lay)
        return f.build(jnp.asarray(_pow2(keys), f.kdtype))

    run_a = Run(np.unique(keys_a), [0] * len(np.unique(keys_a)),
                np.zeros(len(np.unique(keys_a)), bool), 0, layout_a,
                build(layout_a, np.unique(keys_a)))
    run_b = Run(np.unique(keys_b), [0] * len(np.unique(keys_b)),
                np.zeros(len(np.unique(keys_b)), bool), 1, layout_b,
                build(layout_b, np.unique(keys_b)))
    union = np.unique(np.concatenate([keys_a, keys_b]))

    state, how = merge_filter_state([run_a, run_b], target, union, build)
    via_or = how == "or"
    assert via_or == (layout_a == target and layout_b == target)
    _check_union_no_fn(target, state, union)
    if via_or:
        # same-layout OR *is* the bulk rebuild, bit for bit
        np.testing.assert_array_equal(np.asarray(state),
                                      np.asarray(build(target, union)))
    return state


def _random_multiseg_layout(rng, d):
    """Mixed-Δ multi-segment layout (the shapes compaction can meet)."""
    deltas, rem = [], d
    for _ in range(int(rng.integers(2, 4))):
        if rem < 1:
            break
        deltas.append(int(min(rng.integers(1, 8), rem)))
        rem -= deltas[-1]
    k = len(deltas)
    return FilterLayout(
        d=d, deltas=tuple(deltas),
        replicas=tuple(int(r) for r in rng.integers(1, 3, k)),
        seg_of_layer=tuple(int(s) for s in rng.integers(0, 2, k)),
        seg_bits=(4096, 2048), seed=int(rng.integers(1 << 30)))


def _seeded_cases(seed):
    rng = np.random.default_rng(seed)
    d = int(rng.integers(16, 33))
    hi = (1 << d) - 1
    keys_a = rng.integers(0, hi, int(rng.integers(1, 400)), dtype=np.uint64)
    keys_b = rng.integers(0, hi, int(rng.integers(1, 400)), dtype=np.uint64)
    same = basic_layout(d, 256, 14.0, delta=int(rng.integers(1, 8)),
                        seed=seed)
    # same-layout OR merge
    _merge_case(same, same, same, keys_a, keys_b)
    # cross-layout rebuild into a larger class
    bigger = basic_layout(d, 2048, 14.0, delta=int(rng.integers(1, 8)),
                          seed=seed)
    _merge_case(same, same, bigger, keys_a, keys_b)
    # mixed multi-segment sources rebuilt into a multi-segment target
    la = _random_multiseg_layout(rng, d)
    lb = _random_multiseg_layout(rng, d)
    lt = _random_multiseg_layout(rng, d)
    _merge_case(la, lb, lt, keys_a, keys_b)
    _merge_case(la, la, la, keys_a, keys_b)     # multi-segment OR merge


@pytest.mark.parametrize("seed", [11, 22, 33, 44])
def test_merge_invariant_seeded(seed):
    _seeded_cases(seed)


@pytest.mark.parametrize("seed", [7, 19, 42])
def test_promote_merge_invariant(seed):
    """Promotion merges (deletable stores' in-place growth) admit no false
    negatives and distribute over OR: promote(a|b) == promote(a)|promote(b)."""
    from repro.core import promote_state, promotion_factors

    rng = np.random.default_rng(seed)
    d = int(rng.integers(16, 33))
    hi = (1 << d) - 1
    keys_a = rng.integers(0, hi, 300, dtype=np.uint64)
    keys_b = rng.integers(0, hi, 300, dtype=np.uint64)
    small = basic_layout(d, 256, 14.0, delta=6, seed=seed)
    big = basic_layout(d, 1024, 14.0, delta=6, seed=seed)
    assert promotion_factors(small, big) is not None

    def build(lay, keys):
        f = BloomRF(lay)
        return f.build(jnp.asarray(keys, f.kdtype))

    ka, kb = np.unique(keys_a), np.unique(keys_b)
    run_a = Run(ka, [0] * len(ka), np.zeros(len(ka), bool), 0, small,
                build(small, ka))
    run_b = Run(kb, [0] * len(kb), np.zeros(len(kb), bool), 1, small,
                build(small, kb))
    union = np.unique(np.concatenate([ka, kb]))
    state, how = merge_filter_state([run_a, run_b], big, union, build,
                                    allow_promote=True)
    assert how == "promote"
    _check_union_no_fn(big, state, union)
    # promotion distributes over OR — merged-then-promoted is bit-identical
    ored = jnp.bitwise_or(run_a.state, run_b.state)
    np.testing.assert_array_equal(np.asarray(state),
                                  np.asarray(promote_state(ored, small, big)))
    # without allow_promote the same inputs fall back to a rebuild
    _, how2 = merge_filter_state([run_a, run_b], big, union, build)
    assert how2 == "rebuild"


def test_store_compaction_end_to_end_no_fn(rng):
    """Drive a real store through flushes/compactions with deletes and
    re-inserts; every live key must stay reachable (point + range)."""
    st = Store(StoreConfig(d=24, memtable_limit=64, level0_runs=2,
                           fanout=3, bits_per_key=12.0))
    model = {}
    for i in range(4000):
        k = int(rng.integers(0, 1 << 24))
        if i % 11 == 0 and model:
            dk = int(rng.integers(0, 1 << 24))
            st.delete(dk)
            model.pop(dk, None)
        else:
            st.put(k, i)
            model[k] = i
    st.flush()
    assert st.stats.or_merges + st.stats.rebuild_merges > 0
    live = np.fromiter(model.keys(), np.uint64, len(model))
    assert st.get_many(live) == [model[int(k)] for k in live]
    # straddling scans find their keys
    sample = live[rng.integers(0, len(live), 100)]
    res = st.scan_many(np.maximum(sample, 2) - 2,
                       np.minimum(sample + 2, (1 << 24) - 1))
    for k, r in zip(sample, res):
        assert any(kk == int(k) for kk, _ in r)


# ---------------------------------------------------------------------------
# hypothesis exploration (optional locally, installed in CI — only these
# tests skip without it; the seeded suite above always runs)
# ---------------------------------------------------------------------------

if hst is not None:
    _settings = settings(max_examples=25, deadline=None)

    @_settings
    @given(
        d=hst.sampled_from([16, 20, 24, 32]),
        delta_a=hst.integers(1, 7),
        delta_t=hst.integers(1, 7),
        seed=hst.integers(0, 2 ** 16),
        data=hst.data(),
    )
    def test_merged_filter_never_false_negative(d, delta_a, delta_t, seed,
                                                data):
        rng = np.random.default_rng(seed)
        hi = (1 << d) - 1
        na = data.draw(hst.integers(1, 120))
        nb = data.draw(hst.integers(1, 120))
        keys_a = rng.integers(0, hi, na, dtype=np.uint64)
        keys_b = rng.integers(0, hi, nb, dtype=np.uint64)
        src = basic_layout(d, 128, 12.0, delta=delta_a, seed=seed + 1)
        _merge_case(src, src, src, keys_a, keys_b)      # OR path
        tgt = basic_layout(d, 1024, 12.0, delta=delta_t, seed=seed + 1)
        _merge_case(src, src, tgt, keys_a, keys_b)      # rebuild path

    @_settings
    @given(seed=hst.integers(0, 2 ** 16))
    def test_merged_multiseg_filters_never_false_negative(seed):
        _seeded_cases(seed)
