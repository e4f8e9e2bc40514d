"""Plan->gather->combine engine (core/engine.py) vs the pre-refactor
reference path: bit-identity on randomized layouts, the single-gather jaxpr
invariant, the deduped word-access model, and the lane-packed scatter.

The reference is ``BloomRF.point_reference`` / ``range_reference`` (per-key
scalar probes under vmap — the exact pre-engine implementation), so these
are cross-implementation checks, not self-comparisons.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BloomRF, FilterLayout, basic_layout


def _count_gathers(jaxpr) -> int:
    """Gather ops in a jaxpr, recursing into sub-jaxprs (pjit/while/...)."""
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


def _random_layout(rng, allow_exact=False):
    """Random layout: d <= 32, 2 hashed segments, replicas, Δ in 1..7."""
    d = int(rng.integers(16, 33))
    deltas, rem = [], d
    for _ in range(int(rng.integers(2, 5))):
        if rem < 1:
            break
        deltas.append(int(min(rng.integers(1, 8), rem)))
        rem -= deltas[-1]
    k = len(deltas)
    exact_seg = None
    seg_bits = (8192, 4096)
    seg_of_layer = tuple(int(s) for s in rng.integers(0, 2, k))
    if allow_exact and d - sum(deltas) >= 4 and rng.integers(2):
        exact_seg = 2
        seg_bits = (8192, 4096, 1 << (d - sum(deltas)))
    return FilterLayout(
        d=d, deltas=tuple(deltas),
        replicas=tuple(int(r) for r in rng.integers(1, 3, k)),
        seg_of_layer=seg_of_layer, seg_bits=seg_bits, exact_seg=exact_seg,
        seed=int(rng.integers(1 << 30)))


def _compare(lay, trng, n_keys=1500, n_q=20_000):
    f = BloomRF(lay)
    hi_excl = 1 << lay.d if lay.d < 64 else (1 << 63)
    keys = trng.integers(0, hi_excl, n_keys, dtype=np.uint64)
    state = f.build(jnp.asarray(keys, f.kdtype))
    lo = trng.integers(0, hi_excl, n_q, dtype=np.uint64)
    span = trng.integers(0, 1 << min(lay.d - 1, 14), n_q, dtype=np.uint64)
    hi = np.minimum(lo + span, hi_excl - 1)
    want = np.asarray(f.range_reference(state, jnp.asarray(lo, f.kdtype),
                                        jnp.asarray(hi, f.kdtype)))
    got = np.asarray(f.range(state, jnp.asarray(lo, f.kdtype),
                             jnp.asarray(hi, f.kdtype)))
    np.testing.assert_array_equal(want, got, err_msg=lay.describe())
    qs = trng.integers(0, hi_excl, n_q // 2, dtype=np.uint64)
    wp = np.asarray(f.point_reference(state, jnp.asarray(qs, f.kdtype)))
    gp = np.asarray(f.point(state, jnp.asarray(qs, f.kdtype)))
    np.testing.assert_array_equal(wp, gp, err_msg=lay.describe())


# ---------------------------------------------------------------------------
# bit-identity: engine vs pre-refactor reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("delta", [1, 2, 3, 4, 5, 6])
def test_engine_bit_identical_delta_sweep(delta):
    trng = np.random.default_rng(0xE0 + delta)
    _compare(basic_layout(24, 800, 14.0, delta=delta), trng, 800, 8000)


def test_engine_bit_identical_100k_queries():
    """Acceptance: >= 1e5 randomized queries, engine == _range_one."""
    trng = np.random.default_rng(0xE17)
    _compare(basic_layout(32, 2000, 14.0, delta=6), trng, 2000, 100_000)


def test_engine_bit_identical_100k_queries_w64_replicas():
    """Δ=7 (two-lane words) + replicas, >= 1e5 queries."""
    trng = np.random.default_rng(0xE18)
    lay = FilterLayout(d=32, deltas=(7, 7), replicas=(1, 2),
                       seg_of_layer=(0, 0), seg_bits=(16384,))
    _compare(lay, trng, 2000, 100_000)


def test_engine_bit_identical_100k_queries_exact_layout():
    """Exact-bitmap layout (fused exact covering bits + dynamic mid scan)."""
    trng = np.random.default_rng(0xE19)
    lay = FilterLayout(d=32, deltas=(7, 7, 4, 2), replicas=(1, 1, 1, 2),
                       seg_of_layer=(2, 2, 1, 1),
                       seg_bits=(1 << 12, 4096, 8192), exact_seg=0)
    _compare(lay, trng, 1000, 100_000)


@pytest.mark.parametrize("trial", range(8))
def test_engine_random_layouts_property(trial):
    """Randomized layouts: Δ in 1..7, replicas > 1, multi-segment, exact."""
    trng = np.random.default_rng(0xEA5E + trial)
    _compare(_random_layout(trng, allow_exact=True), trng)


def test_engine_64bit_domain():
    trng = np.random.default_rng(0xE64)
    _compare(basic_layout(64, 2000, 16.0, delta=7), trng, 2000, 20_000)


# ---------------------------------------------------------------------------
# plan accounting: the deduped access model and the gather width
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: basic_layout(32, 2000, 14.0, delta=6),
    lambda: basic_layout(64, 2000, 16.0, delta=7),
    lambda: FilterLayout(d=32, deltas=(7, 7), replicas=(1, 2),
                         seg_of_layer=(0, 0), seg_bits=(16384,)),
    lambda: FilterLayout(d=32, deltas=(7, 7, 4, 2), replicas=(1, 1, 1, 2),
                         seg_of_layer=(2, 2, 1, 1),
                         seg_bits=(1 << 12, 4096, 8192), exact_seg=0),
])
def test_gather_width_matches_access_model(make):
    lay = make()
    f = BloomRF(lay)
    eng = f.engine
    # the static model counts the engine's planned word loads, plus one
    # amortized lane for the exact middle scan (not a planned gather)
    scan = 1 if (lay.has_exact and lay.top_level < lay.d) else 0
    assert f.word_accesses_per_range_query() == eng.range_word_loads + scan
    # planned loads == 4 per layer per replica (covering bits deduped away)
    hashed = sum(4 * lay.replicas[i] for i in range(lay.k))
    exact = 2 if (lay.has_exact and lay.top_level < lay.d) else 0
    assert eng.range_word_loads == hashed + exact
    # the actual plan's gather width A == the static accounting
    lo = jnp.zeros(7, f.kdtype)
    hi = jnp.full(7, (1 << min(lay.d, 63)) - 1, f.kdtype)
    plan = eng.plan_range(lo, hi)
    assert plan.lanes.shape == (7, eng.range_gather_width)
    # lanes-vs-words: W=64 words take two lanes each, everything else one
    lanes = sum(4 * lay.replicas[i] * (2 if lay.word_bits(i) == 64 else 1)
                for i in range(lay.k)) + exact
    assert eng.range_gather_width == lanes


def test_point_word_accesses_unchanged():
    lay = basic_layout(64, 10_000, 16.0, delta=7)
    f = BloomRF(lay)
    assert f.word_accesses_per_point_query() == lay.k
    qs = jnp.zeros(3, f.kdtype)
    assert f.engine.plan_point(qs).lanes.shape == (3, lay.k)


# ---------------------------------------------------------------------------
# the single fused gather (jaxpr inspection)
# ---------------------------------------------------------------------------

def test_range_probe_single_gather_jaxpr():
    """The batched range probe must contain exactly ONE gather over the
    filter state per probe tile (hashed-only layouts)."""
    lay = basic_layout(32, 2000, 14.0, delta=6)
    f = BloomRF(lay)
    state = f.init_state()
    lo = jnp.zeros(512, jnp.uint32)
    hi = jnp.ones(512, jnp.uint32)
    jaxpr = jax.make_jaxpr(f.range)(state, lo, hi)
    assert _count_gathers(jaxpr.jaxpr) == 1, jaxpr.pretty_print()
    jaxpr_p = jax.make_jaxpr(f.point)(state, lo)
    assert _count_gathers(jaxpr_p.jaxpr) == 1
    # the reference path is the many-gather graph the engine replaced
    jaxpr_ref = jax.make_jaxpr(f.range_reference)(state, lo, hi)
    assert _count_gathers(jaxpr_ref.jaxpr) > 1


def test_multisegment_replicas_single_gather_jaxpr():
    lay = FilterLayout(d=32, deltas=(6, 5, 4), replicas=(2, 1, 2),
                       seg_of_layer=(0, 1, 0), seg_bits=(8192, 4096))
    f = BloomRF(lay)
    jaxpr = jax.make_jaxpr(f.range)(f.init_state(),
                                    jnp.zeros(64, jnp.uint32),
                                    jnp.ones(64, jnp.uint32))
    assert _count_gathers(jaxpr.jaxpr) == 1


# ---------------------------------------------------------------------------
# partitioned range kernel parity (resident vs partitioned vs XLA)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile", [32, 512])
def test_range_probe_partitioned_parity(rng, tile):
    from repro.kernels import (FilterOps, range_probe_partitioned,
                               range_probe_resident)
    from repro.kernels import ref as kref

    lay = basic_layout(32, 5000, 14.0, delta=6)
    f = BloomRF(lay)
    keys = rng.integers(0, 1 << 32, 5000, dtype=np.uint64).astype(np.uint32)
    state = f.build(jnp.asarray(keys))
    lo = rng.integers(0, 1 << 32, 900, dtype=np.uint64).astype(np.uint32)
    hi = np.maximum(lo, lo + rng.integers(0, 1 << 12, 900).astype(np.uint32))
    want = np.asarray(kref.range_ref(lay, state, jnp.asarray(lo),
                                     jnp.asarray(hi)))
    part = np.asarray(range_probe_partitioned(
        lay, state, jnp.asarray(lo), jnp.asarray(hi), tile, True))
    np.testing.assert_array_equal(want, part)
    res = np.asarray(range_probe_resident(
        lay, state, jnp.asarray(lo), jnp.asarray(hi), 256, True))
    np.testing.assert_array_equal(part, res)
    # dispatcher: forced-HBM ops must take the partitioned path and agree
    ops = FilterOps(lay, interpret=True, vmem_budget_u32=1)
    assert not ops.resident
    via_ops = np.asarray(ops.range(state, jnp.asarray(lo), jnp.asarray(hi)))
    np.testing.assert_array_equal(want, via_ops)
    # no false negatives through the kernel: straddling ranges all positive
    slo = np.maximum(keys.astype(np.int64) - 2, 0).astype(np.uint32)
    shi = np.minimum(keys.astype(np.int64) + 2, (1 << 32) - 1).astype(np.uint32)
    assert np.asarray(range_probe_partitioned(
        lay, state, jnp.asarray(slo), jnp.asarray(shi), tile, True)).all()


def test_range_probe_partitioned_rejects_exact():
    from repro.core.tuning import advise
    from repro.kernels import range_probe_partitioned

    lay = advise(16, 300, 16384, 64.0).layout
    assert lay.has_exact
    f = BloomRF(lay)
    state = f.build(jnp.asarray(np.arange(300, dtype=np.uint32)))
    lo = jnp.asarray(np.arange(10, dtype=np.uint32))
    with pytest.raises(ValueError, match="exact-layer"):
        range_probe_partitioned(lay, state, lo, lo, 128, True)


# ---------------------------------------------------------------------------
# lane-packed scatter_or (the O(total_bits) transient is gone)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scatter_or_matches_bitmap_path(seed):
    trng = np.random.default_rng(0x5CA7 + seed)
    lay = basic_layout(32, 3000, 14.0, delta=6)
    f = BloomRF(lay)
    keys = jnp.asarray(trng.integers(0, 1 << 32, 3000, dtype=np.uint64),
                       f.kdtype)
    pos = jax.vmap(f._positions_one)(keys).reshape(-1)
    packed = f.scatter_or(f.init_state(), pos)
    bitmap = f.scatter_or(f.init_state(), pos, bitmap=True)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(bitmap))
    # masked variant (the sharded banks' ownership masks)
    vals = jnp.asarray(trng.integers(0, 2, pos.shape[0]).astype(bool))
    packed = f.scatter_or(f.init_state(), pos, vals)
    bitmap = f.scatter_or(f.init_state(), pos, vals, bitmap=True)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(bitmap))
    # heavy duplicates (bulk insert of one repeated key)
    dup = jnp.tile(pos[:7], 400)
    packed = f.scatter_or(f.init_state(), dup)
    bitmap = f.scatter_or(f.init_state(), dup, bitmap=True)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(bitmap))


def test_insert_has_no_total_bits_transient():
    """The bulk-insert jaxpr must not materialise an O(total_bits) bool
    temp; peak intermediate size stays O(keys * probes + total_u32)."""
    lay = basic_layout(32, 2_000_000, 16.0, delta=6)
    f = BloomRF(lay)
    keys = jnp.zeros(1024, jnp.uint32)

    def big_bool_consts(jaxpr, floor):
        out = []
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                sz = getattr(var.aval, "size", 0)
                if var.aval.dtype == jnp.bool_ and sz >= floor:
                    out.append((eqn.primitive.name, sz))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    out += big_bool_consts(v.jaxpr, floor)
        return out

    jaxpr = jax.make_jaxpr(f.insert)(f.init_state(), keys)
    assert lay.total_bits >= 32_000_000  # the old path's transient size
    offenders = big_bool_consts(jaxpr.jaxpr, lay.total_bits)
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# multi-filter stacked plan (StackedProbe): R rows, one gather
# ---------------------------------------------------------------------------

def _stack_case(rng, layouts):
    from repro.core import stacked_probe
    filts = [BloomRF(lay) for lay in layouts]
    rows = [f.build(jnp.asarray(
        rng.integers(0, 1 << f.layout.d, 1500, dtype=np.uint64), f.kdtype))
        for f in filts]
    flat = jnp.concatenate(rows)
    bases = tuple(int(b) for b in np.cumsum(
        [0] + [lay.total_u32 for lay in layouts[:-1]]))
    return stacked_probe(tuple(layouts), bases), filts, rows, flat


def test_stacked_probe_bit_identical_mixed_layouts(rng):
    layouts = [basic_layout(32, 1500, 14.0, delta=6),
               basic_layout(32, 1500, 14.0, delta=6),
               basic_layout(32, 6000, 14.0, delta=5),
               basic_layout(32, 24000, 12.0, delta=7)]
    sp, filts, rows, flat = _stack_case(rng, layouts)
    assert len(sp.spans) == 3            # two same-layout rows share a span
    lo = rng.integers(0, 1 << 32, 20000, dtype=np.uint64).astype(np.uint32)
    hi = np.minimum(lo.astype(np.uint64) + (1 << 12),
                    (1 << 32) - 1).astype(np.uint32)
    got = np.asarray(sp.range_all(flat, jnp.asarray(lo), jnp.asarray(hi)))
    for j, (f, row) in enumerate(zip(filts, rows)):
        want = np.asarray(f.range(row, jnp.asarray(lo), jnp.asarray(hi)))
        np.testing.assert_array_equal(got[:, j], want, err_msg=f"row {j}")
    qs = jnp.asarray(rng.integers(0, 1 << 32, 20000,
                                  dtype=np.uint64).astype(np.uint32))
    gp = np.asarray(sp.point_all(flat, qs))
    for j, (f, row) in enumerate(zip(filts, rows)):
        np.testing.assert_array_equal(gp[:, j], np.asarray(f.point(row, qs)))


def test_stacked_probe_per_row_bounds(rng):
    layouts = [basic_layout(32, 2000, 14.0, delta=6)] * 3
    sp, filts, rows, flat = _stack_case(rng, layouts)
    lo = rng.integers(0, 1 << 32, (4000, 3), dtype=np.uint64).astype(np.uint32)
    hi = np.minimum(lo.astype(np.uint64) + 2000,
                    (1 << 32) - 1).astype(np.uint32)
    got = np.asarray(sp.range_all(flat, jnp.asarray(lo), jnp.asarray(hi)))
    for j, (f, row) in enumerate(zip(filts, rows)):
        want = np.asarray(f.range(row, jnp.asarray(lo[:, j]),
                                  jnp.asarray(hi[:, j])))
        np.testing.assert_array_equal(got[:, j], want)


def test_stacked_probe_single_gather_jaxpr(rng):
    layouts = [basic_layout(32, 1000, 14.0, delta=6),
               basic_layout(32, 4000, 14.0, delta=4),
               basic_layout(32, 1000, 14.0, delta=6)]
    sp, _, _, flat = _stack_case(rng, layouts)
    lo = jnp.zeros(256, jnp.uint32)
    hi = jnp.full(256, 9999, jnp.uint32)
    jaxpr = jax.make_jaxpr(sp._range_all)(flat, lo, hi)
    assert _count_gathers(jaxpr.jaxpr) == 1, jaxpr.pretty_print()
    jaxpr_p = jax.make_jaxpr(sp._point_all)(flat, lo)
    assert _count_gathers(jaxpr_p.jaxpr) == 1
    # per-row bounds keep the invariant
    lo2 = jnp.zeros((256, 3), jnp.uint32)
    hi2 = jnp.full((256, 3), 9999, jnp.uint32)
    jaxpr2 = jax.make_jaxpr(sp._range_all)(flat, lo2, hi2)
    assert _count_gathers(jaxpr2.jaxpr) == 1


def test_stacked_probe_validation():
    from repro.core import StackedProbe, stacked_probe
    lay = basic_layout(32, 1000, 14.0, delta=6)
    with pytest.raises(ValueError, match="at least one"):
        StackedProbe((), ())
    with pytest.raises(ValueError, match="row bases"):
        stacked_probe((lay, lay), (0,))
    exact = FilterLayout(d=16, deltas=(7, 4), replicas=(1, 1),
                         seg_of_layer=(1, 1), seg_bits=(1 << 5, 8192),
                         exact_seg=0)
    with pytest.raises(ValueError, match="exact-bitmap"):
        stacked_probe((exact,), (0,))
    sp = stacked_probe((lay,), (0,))
    with pytest.raises(ValueError, match="bounds"):
        sp._range_all(jnp.zeros(lay.total_u32, jnp.uint32),
                      jnp.zeros((4, 7), jnp.uint32),
                      jnp.zeros((4, 7), jnp.uint32))


def test_filter_ops_stacked_dispatch_parity(rng):
    from repro.kernels import FilterOps
    lay = basic_layout(32, 2000, 14.0, delta=6)
    f = BloomRF(lay)
    rows = [f.build(jnp.asarray(
        rng.integers(0, 1 << 32, 2000, dtype=np.uint64).astype(np.uint32)))
        for _ in range(5)]
    stack = jnp.stack(rows)
    lo = rng.integers(0, 1 << 32, 600, dtype=np.uint64).astype(np.uint32)
    hi = np.maximum(lo, lo + (1 << 11)).astype(np.uint32)
    qs = jnp.asarray(rng.integers(0, 1 << 32, 600,
                                  dtype=np.uint64).astype(np.uint32))
    want_r = np.stack([np.asarray(f.range(r, jnp.asarray(lo),
                                          jnp.asarray(hi))) for r in rows],
                      axis=1)
    want_p = np.stack([np.asarray(f.point(r, qs)) for r in rows], axis=1)
    # resident Pallas kernel path vs forced XLA stacked path
    for budget in (None, 1):
        ops = FilterOps(lay, interpret=True, vmem_budget_u32=budget)
        np.testing.assert_array_equal(
            np.asarray(ops.range_stacked(stack, jnp.asarray(lo),
                                         jnp.asarray(hi))), want_r)
        np.testing.assert_array_equal(np.asarray(ops.point_stacked(stack, qs)),
                                      want_p)


def test_vmem_budget_knob():
    from repro.kernels import DEFAULT_VMEM_BUDGET_U32, FilterOps
    lay = basic_layout(32, 2000, 14.0, delta=6)
    assert FilterOps(lay).vmem_budget_u32 == DEFAULT_VMEM_BUDGET_U32
    assert FilterOps(lay).resident
    forced = FilterOps(lay, vmem_budget_u32=lay.total_u32 - 1)
    assert not forced.resident           # threshold is a real dispatch knob
    assert FilterOps(lay, vmem_budget_u32=lay.total_u32).resident


def test_insert_online_and_build_np_still_agree(rng):
    lay = basic_layout(32, 500, bits_per_key=12.0, delta=6)
    f = BloomRF(lay)
    keys = rng.integers(0, (1 << 32) - 1, 500, dtype=np.uint64)
    bulk = f.build(jnp.asarray(keys, f.kdtype))
    online = f.insert_online(f.init_state(), jnp.asarray(keys, f.kdtype))
    np.testing.assert_array_equal(np.asarray(bulk), np.asarray(online))
    np.testing.assert_array_equal(np.asarray(bulk),
                                  np.asarray(f.build_np(keys)))
