"""The typed filter façade (repro/api.py, DESIGN.md §11).

Covers: FilterSpec validation, codec threading on every probe surface
(single / bank / tenant / store), the preserved one-fused-gather jaxpr
invariant behind the façade, the legacy-constructor deprecation map, the
validated BLOOMRF_VMEM_BUDGET_U32 knob, and the vectorized seeds_np.
"""
import warnings

import jax
import numpy as np
import pytest

import repro
from repro.api import FilterSpec, open_filter
from test_engine import _count_gathers


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(dtype="u128"),
    dict(placement="cluster"),
    dict(backend="gpu"),
    dict(tuning="magic"),
    dict(n=0),
    dict(bits_per_key=12.0, target_fpr=0.01),
    dict(bits_per_key=-1.0),
    dict(target_fpr=1.5),
    dict(dtype="u32", range_log2=40),
    dict(delta=9),
    dict(shards=3),
    dict(tenants=0),
    dict(chunk=0),
    dict(chunk=-1),
    dict(backend="resident", placement="bank"),
    dict(backend="stacked", placement="single"),
    dict(tuning="advised", placement="store"),
])
def test_spec_rejects_bad_fields(kw):
    with pytest.raises(ValueError, match="FilterSpec"):
        FilterSpec(**kw)


def test_spec_target_fpr_sizing():
    spec = FilterSpec(dtype="u32", n=10_000, target_fpr=0.05, range_log2=10)
    bpk = spec.resolved_bits_per_key()
    assert 6 <= bpk <= 40
    from repro.core.model import basic_range_fpr

    assert basic_range_fpr(32, 10_000, bpk * 10_000, 2.0 ** 10,
                           delta=7) <= 0.05
    # default sizing without either knob
    assert FilterSpec().resolved_bits_per_key() == 16.0
    assert "b/key" in spec.describe()


def test_open_filter_requires_spec():
    with pytest.raises(TypeError):
        open_filter({"dtype": "u64"})


# ---------------------------------------------------------------------------
# deprecation map: every legacy constructor warns, the façade never does
# ---------------------------------------------------------------------------

def _legacy_constructors():
    from repro.core import BloomRF, basic_layout
    from repro.dist.filter_bank import FilterBank
    from repro.dist.tenant_bank import TenantFilterBank
    from repro.kernels import FilterOps
    from repro.store import Store

    lay = basic_layout(32, 1000, 12.0, delta=6)
    return [
        ("BloomRF", lambda: BloomRF(lay)),
        ("FilterOps", lambda: FilterOps(lay)),
        ("FilterBank", lambda: FilterBank(32, 4, 1000)),
        ("TenantFilterBank", lambda: TenantFilterBank(32, 2, 2, 500)),
        ("Store", lambda: Store(d=32)),
    ]


@pytest.mark.parametrize("name,ctor",
                         _legacy_constructors(),
                         ids=[n for n, _ in _legacy_constructors()])
def test_legacy_constructor_warns_with_spec_equivalent(name, ctor):
    with pytest.warns(repro.LegacyAPIWarning, match="FilterSpec"):
        ctor()


@pytest.mark.parametrize("spec", [
    FilterSpec(dtype="u32", n=1000),
    FilterSpec(dtype="u32", n=1000, backend="xla"),
    FilterSpec(dtype="f64", n=1000, placement="bank", shards=2),
    FilterSpec(dtype="u32", n=500, placement="tenant", tenants=2, shards=2),
    FilterSpec(dtype="f32", placement="store", memtable_limit=64),
], ids=["single", "single-xla", "bank", "tenant", "store"])
def test_facade_emits_no_deprecation_warnings(spec):
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        open_filter(spec)


# ---------------------------------------------------------------------------
# one fused gather behind the façade (single, bank, store placements)
# ---------------------------------------------------------------------------

def test_single_placement_one_gather_jaxpr():
    h = open_filter(FilterSpec(dtype="u32", n=5_000, backend="xla"))
    h.insert(np.arange(100, dtype=np.uint64))
    lo = np.arange(16, dtype=np.uint64)
    hi = lo + 7
    import jax.numpy as jnp

    kd = h.filter.kdtype
    jx = jax.make_jaxpr(h.filter.range)(h.state, jnp.asarray(lo, kd),
                                        jnp.asarray(hi, kd))
    assert _count_gathers(jx.jaxpr) == 1, jx.pretty_print()
    jp = jax.make_jaxpr(h.filter.point)(h.state, jnp.asarray(lo, kd))
    assert _count_gathers(jp.jaxpr) == 1


def test_bank_placement_one_gather_jaxpr():
    h = open_filter(FilterSpec(dtype="u32", n=5_000, placement="bank",
                               shards=4))
    import jax.numpy as jnp

    kd = h.bank.kdtype
    lo = jnp.asarray(np.arange(16, dtype=np.uint64), kd)
    hi = lo + 7
    jx = jax.make_jaxpr(h.bank.range)(h.state, lo, hi)
    assert _count_gathers(jx.jaxpr) == 1, jx.pretty_print()
    jp = jax.make_jaxpr(h.bank.point)(h.state, lo)
    assert _count_gathers(jp.jaxpr) == 1


def test_store_placement_one_gather_jaxpr():
    h = open_filter(FilterSpec(dtype="f32", placement="store",
                               memtable_limit=128, level0_runs=8))
    rng = np.random.default_rng(5)
    for v in rng.normal(0, 100, 700).astype(np.float32):
        h.put(float(v), 0)
    h.flush()
    store = h.store
    assert store.n_runs >= 2          # a real multi-run stack
    store._refresh()
    import jax.numpy as jnp

    lo = jnp.zeros(16, store.kdtype)
    hi = lo + 77
    from repro.store import scan_plane

    jx = jax.make_jaxpr(scan_plane.range_all)(store._run_stack(), lo, hi)
    assert _count_gathers(jx.jaxpr) == 1, jx.pretty_print()
    jp = jax.make_jaxpr(scan_plane.point_all)(store._run_stack(), lo)
    assert _count_gathers(jp.jaxpr) == 1


# ---------------------------------------------------------------------------
# typed round-trips: f64 / str / multiattr, façade probes + Store.scan
# (together > 1e5 fuzz ops, zero false negatives everywhere)
# ---------------------------------------------------------------------------

def test_float64_roundtrip_filter_bank_store(rng):
    n, q = 20_000, 20_000
    keys = rng.normal(0.0, 1e6, n)
    single = open_filter(FilterSpec(dtype="f64", n=n, backend="xla"))
    single.insert(keys)
    assert single.point(keys).all()                       # n point ops
    lo = keys - rng.uniform(0.0, 10.0, n)
    hi = keys + rng.uniform(0.0, 10.0, n)
    assert single.range(lo, hi).all()                     # n range ops

    bank = open_filter(FilterSpec(dtype="f64", n=n, placement="bank",
                                  shards=4))
    bank.insert(keys)
    assert bank.point(keys[:q]).all()
    assert bank.range(lo[:q], hi[:q]).all()

    # LSM store: float put -> scan windows must return every stored key
    ts = open_filter(FilterSpec(dtype="f64", placement="store",
                                memtable_limit=512))
    stored = keys[:3_000]
    for i, v in enumerate(stored):
        ts.put(float(v), i)
    ts.flush()
    got = ts.get(float(stored[7]))
    assert got is not None
    centers = stored[rng.integers(0, len(stored), 2_000)]
    res = ts.scan_many(centers - 1.0, centers + 1.0)
    su = np.sort(np.unique(stored))
    for c, rows in zip(centers, res):
        found = {k for k, _ in rows}
        i0, i1 = np.searchsorted(su, [c - 1.0, c + 1.0])
        expect = set(su[i0:i1].tolist()) | ({float(c)} if c in su else set())
        missing = {e for e in expect if c - 1.0 <= e <= c + 1.0} - found
        assert not missing, f"store scan missed float keys: {missing}"


def test_string_roundtrip_filter_and_store(rng):
    import random

    pr = random.Random(17)
    words = list({"".join(pr.choices("abcdefgxyz", k=pr.randint(0, 12)))
                  for _ in range(3_000)})
    single = open_filter(FilterSpec(dtype="str", n=len(words),
                                    backend="xla"))
    single.insert(words)
    assert single.point(words).all()
    # ranges straddling each inserted string (string order)
    assert single.range(words, words).all()
    assert single.range([w[:-1] if w else "" for w in words],
                        [w + "~" for w in words]).all()

    ss = open_filter(FilterSpec(dtype="str", placement="store",
                                memtable_limit=256))
    stored = sorted(words[:1_000])
    for i, w in enumerate(stored):
        ss.put(w, i)
    ss.flush()
    assert ss.get(stored[3]) is not None
    for _ in range(300):
        i = pr.randrange(len(stored))
        j = min(i + pr.randrange(20), len(stored) - 1)
        lo, hi = stored[i], stored[j]
        got = [k for k, _ in ss.scan(lo, hi)]
        assert got == stored[i:j + 1], (lo, hi)   # exact: no FN, no FP


def test_multiattr_roundtrip_filter_and_store(rng):
    n = 10_000
    a = rng.integers(0, 1 << 16, n, dtype=np.uint64)
    b = rng.integers(0, 1 << 31, n, dtype=np.uint64)
    single = open_filter(FilterSpec(dtype="multiattr", n=n, backend="xla"))
    single.insert((a, b))
    assert single.point((a, b)).all()
    # A == a AND B in [b-δ, b+δ] through the <A,B> codes
    blo = np.maximum(b, 5) - 5
    bhi = np.minimum(b + 5, np.uint64((1 << 32) - 1))
    assert single.range((a, blo), (a, bhi)).all()
    # mirrored predicate through the <B,A> codes
    alo = np.maximum(a, 3) - 3
    ahi = np.minimum(a + 3, np.uint64((1 << 32) - 1))
    assert single.range_where_b(b, alo, ahi).all()

    ms = open_filter(FilterSpec(dtype="multiattr", placement="store",
                                memtable_limit=256))
    for i in range(2_000):
        ms.put((int(a[i]), int(b[i])), i)
    ms.flush()
    assert ms.get((int(a[0]), int(b[0]))) == 0
    # conjunctive scans vs brute force
    pairs = sorted(zip(a[:2_000].tolist(), b[:2_000].tolist()))
    for i in range(0, 1_000, 7):
        qa = int(a[i])
        qlo, qhi = int(blo[i]), int(bhi[i])
        got = {k for k, _ in ms.scan((qa, qlo), (qa, qhi))}
        expect = {(x, y) for x, y in pairs if x == qa and qlo <= y <= qhi}
        assert expect <= got        # FN-free; equality holds too (exact keys)
        assert got == expect


def test_multiattr_scan_many_column_bounds_full_batch():
    """Batched multiattr scans with column-form (a_vec, b_vec) bounds must
    return one result list per query, not truncate to the 2 column rows."""
    ms = open_filter(FilterSpec(dtype="multiattr", placement="store",
                                memtable_limit=64))
    for i in range(100):
        ms.put((i % 10, i), i)
    ms.flush()
    a = np.arange(5, dtype=np.uint64)
    res = ms.scan_many((a, np.zeros(5, np.uint64)),
                       (a, np.full(5, 99, np.uint64)))
    assert len(res) == 5
    for ai, rows in zip(a, res):
        assert rows and all(k[0] == int(ai) for k, _ in rows)


def test_tenant_scalar_tenant_broadcasts_across_chunks():
    """A scalar tenant id must broadcast over probe batches longer than one
    chunk (same semantics as the insert path)."""
    h = open_filter(FilterSpec(dtype="u32", n=64, placement="tenant",
                               tenants=2, shards=2, chunk=8))
    keys = np.arange(20, dtype=np.uint64) * 7 + 3
    h.insert(1, keys)
    assert h.point(1, keys).all()                  # 20 queries, chunk=8
    assert h.range(1, keys, keys + 1).all()
    assert not h.point(0, keys).any()              # isolation intact
    with pytest.raises(ValueError, match="align"):
        h.point(np.zeros(3, np.uint32), keys)      # 3 does not align to 20


# ---------------------------------------------------------------------------
# BLOOMRF_VMEM_BUDGET_U32: validated at read time, both knob paths
# ---------------------------------------------------------------------------

def _kernel_layout():
    from repro.core import basic_layout

    return basic_layout(32, 200_000, 16.0, delta=6)


@pytest.mark.parametrize("bad", ["banana", "", "1.5", "-3", "0"])
def test_vmem_budget_env_validated_at_read_time(monkeypatch, bad):
    from repro.kernels.ops import FilterOps, read_vmem_budget_u32

    monkeypatch.setenv("BLOOMRF_VMEM_BUDGET_U32", bad)
    with pytest.raises(ValueError, match="BLOOMRF_VMEM_BUDGET_U32"):
        read_vmem_budget_u32()
    with pytest.raises(ValueError, match="BLOOMRF_VMEM_BUDGET_U32"):
        FilterOps(_kernel_layout(), _warn=False)


def test_vmem_budget_env_and_override_paths(monkeypatch):
    from repro.kernels.ops import DEFAULT_VMEM_BUDGET_U32, FilterOps

    lay = _kernel_layout()
    monkeypatch.delenv("BLOOMRF_VMEM_BUDGET_U32", raising=False)
    assert FilterOps(lay, _warn=False).vmem_budget_u32 \
        == DEFAULT_VMEM_BUDGET_U32
    # env knob: small budget flips the dispatch to partitioned
    monkeypatch.setenv("BLOOMRF_VMEM_BUDGET_U32", "64")
    ops = FilterOps(lay, _warn=False)
    assert ops.vmem_budget_u32 == 64 and not ops.resident
    # per-instance override beats the env
    ops = FilterOps(lay, vmem_budget_u32=1 << 22, _warn=False)
    assert ops.resident
    # the façade's backend knob rides the same override
    h = open_filter(FilterSpec(dtype="u32", n=200_000,
                               backend="partitioned"))
    assert h.ops is not None and not h.ops.resident
    h = open_filter(FilterSpec(dtype="u32", n=200_000, backend="resident"))
    assert h.ops is not None and h.ops.resident


# ---------------------------------------------------------------------------
# seeds_np vectorization + codec exports
# ---------------------------------------------------------------------------

def test_seeds_np_vectorized_matches_scalar_loop():
    from repro.filters.api import mix64_np, seeds_np

    def reference(base, n):
        out = np.empty(n, np.uint64)
        s = np.uint64(base)
        for i in range(n):
            with np.errstate(over="ignore"):
                s = s + np.uint64(0x9E3779B97F4A7C15)
            out[i] = mix64_np(np.asarray([s]))[0]
        return out

    for base in (0, 1, 0xDEADBEEF, 2 ** 63, 2 ** 64 - 1):
        assert np.array_equal(seeds_np(base, 13), reference(base, 13))
    assert seeds_np(7, 0).shape == (0,)


def test_codec_helpers_exported_from_core():
    import repro.core as core

    for name in ("float64_to_u64", "u64_to_float64", "float32_to_u32",
                 "u32_to_float32", "string_point_code",
                 "string_range_bounds", "pack2", "unpack2", "pack2x32",
                 "unpack2x32", "multiattr_insert_codes",
                 "multiattr_range_for_a_eq_b_range"):
        assert name in core.__all__
        assert callable(getattr(core, name))
