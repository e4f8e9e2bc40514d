"""Store-scan megakernel vs the XLA StackedProbe reference.

``kernels/store_scan.py`` promises verdicts bit-identical to
``StackedProbe.touch_all`` whatever the run mix.  This suite pins that
contract per layout class (mixed deltas, multi-segment, replicas,
promoted/tiled state, capacity-class ladders, TTL generation lanes),
asserts the fused plane really is ONE ``pallas_call`` per scan batch,
and fuzzes a kernel-backed :class:`Store` against an XLA-backed twin
through a deletable-churn op stream — same results, same stats.

Everything runs in interpret mode on CPU (the CI pallas lane)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FilterLayout, basic_layout
from repro.core.dynamic import Generations, promote_layout, promote_state
from repro.core.engine import _filter_for_layout, stacked_probe
from repro.kernels.store_scan import build_run_stack, store_scan_probe
from repro.store import Store, StoreConfig

D = 32
DMAX = (1 << D) - 1


# ---------------------------------------------------------------------------
# layout-class row builders: (layouts, states, kmin, kmax)
# ---------------------------------------------------------------------------

def _filled_rows(layouts, rng, n_per=400):
    """One populated run row per layout + its true key fences."""
    states, kmins, kmaxs = [], [], []
    for lay in layouts:
        f = _filter_for_layout(lay)
        keys = rng.integers(0, DMAX, n_per, dtype=np.uint64)
        states.append(f.insert(f.init_state(), jnp.asarray(keys, jnp.uint32)))
        kmins.append(int(keys.min()))
        kmaxs.append(int(keys.max()))
    return (tuple(layouts), states,
            np.asarray(kmins, np.uint32), np.asarray(kmaxs, np.uint32))


def _mixed_delta(rng):
    return _filled_rows([basic_layout(D, 500, 12.0, delta=dl)
                         for dl in (4, 6, 7)], rng)


def _multi_segment(rng):
    seg = FilterLayout(d=D, deltas=(6, 5, 4), replicas=(1, 1, 1),
                       seg_of_layer=(0, 1, 0), seg_bits=(8192, 4096))
    return _filled_rows([seg, basic_layout(D, 400, 12.0, delta=6), seg], rng)


def _replicas(rng):
    rep = FilterLayout(d=D, deltas=(7, 7), replicas=(1, 2),
                       seg_of_layer=(0, 0), seg_bits=(16384,))
    return _filled_rows([rep, rep, basic_layout(D, 300, 14.0, delta=7)], rng)


def _promoted(rng):
    """A promote-merged run (tiled state) next to rebuilt neighbours."""
    base = basic_layout(D, 400, 12.0, delta=6)
    big = promote_layout(base, 4)
    f = _filter_for_layout(base)
    keys = rng.integers(0, DMAX, 800, dtype=np.uint64)
    small = f.insert(f.init_state(), jnp.asarray(keys, jnp.uint32))
    layouts = (big, basic_layout(D, 1600, 12.0, delta=6))
    _, states, kmins, kmaxs = _filled_rows(layouts[1:], rng)
    return (layouts, [promote_state(small, base, big)] + states,
            np.concatenate([[keys.min()], kmins]).astype(np.uint32),
            np.concatenate([[keys.max()], kmaxs]).astype(np.uint32))


def _capacity_ladder(rng):
    """The store's normal stack: two level-0 rows + two lower levels."""
    c0 = basic_layout(D, 400, 14.0, delta=6)
    return _filled_rows([c0, c0, basic_layout(D, 1600, 14.0, delta=6),
                         basic_layout(D, 6400, 14.0, delta=6)], rng)


def _ttl_lanes(rng):
    """A Generations-collapsed (TTL) state as one of the run rows."""
    lay = basic_layout(D, 600, 12.0, delta=6)
    f = _filter_for_layout(lay)
    gens = Generations(f.init_state, n_generations=3)
    keys = rng.integers(0, DMAX, 600, dtype=np.uint64)
    for part in np.array_split(keys, 4):
        gens.insert(f.insert, jnp.asarray(part, jnp.uint32))
        gens.advance()                  # retire a slot; OR stays union-sound
    layouts = (lay, basic_layout(D, 500, 12.0, delta=5))
    _, states, kmins, kmaxs = _filled_rows(layouts[1:], rng)
    return (layouts, [gens.collapsed] + states,
            np.concatenate([[keys.min()], kmins]).astype(np.uint32),
            np.concatenate([[keys.max()], kmaxs]).astype(np.uint32))


CLASSES = {
    "mixed_delta": _mixed_delta,
    "multi_segment": _multi_segment,
    "replicas": _replicas,
    "promoted": _promoted,
    "capacity_ladder": _capacity_ladder,
    "ttl_lanes": _ttl_lanes,
}


def _queries(rng, b=200):
    """Scan bounds: short/long ranges plus fully-off-fence probes."""
    lo = rng.integers(0, DMAX, b, dtype=np.uint64)
    width = rng.integers(0, 1 << 20, b, dtype=np.uint64)
    hi = np.minimum(lo + width, DMAX)
    lo[:8] = hi[:8] = 0                # below every fence
    lo[8:16] = hi[8:16] = DMAX         # above most fences
    return jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32)


def _reference(layouts, states, kmin, kmax, lo, hi):
    """StackedProbe.touch_all over the unpadded concatenated stack."""
    bases = tuple(int(b) for b in
                  np.cumsum([0] + [s.shape[0] for s in states[:-1]]))
    probe = stacked_probe(tuple(layouts), bases)
    return probe.touch_all(jnp.concatenate(states),
                           jnp.asarray(kmin, jnp.uint32),
                           jnp.asarray(kmax, jnp.uint32), lo, hi)


# ---------------------------------------------------------------------------
# per-layout-class parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", sorted(CLASSES))
@pytest.mark.parametrize("resident", [True, False])
def test_kernel_matches_stacked_probe(rng, cls, resident):
    layouts, states, kmin, kmax = CLASSES[cls](rng)
    lo, hi = _queries(rng)
    f_ref, t_ref = _reference(layouts, states, kmin, kmax, lo, hi)
    stack = build_run_stack(states)
    f_k, t_k = store_scan_probe(layouts, stack,
                                jnp.asarray(kmin), jnp.asarray(kmax),
                                lo, hi, 64, resident, True)
    assert np.array_equal(np.asarray(f_k), np.asarray(f_ref)), cls
    assert np.array_equal(np.asarray(t_k), np.asarray(t_ref)), cls


def test_kernel_odd_batch_and_tiny_tile(rng):
    """B not a multiple of the tile; a tile smaller than one row's plan."""
    layouts, states, kmin, kmax = _capacity_ladder(rng)   # R = 4
    lo, hi = _queries(rng, b=77)
    f_ref, t_ref = _reference(layouts, states, kmin, kmax, lo, hi)
    stack = build_run_stack(states)
    for resident in (True, False):
        f_k, t_k = store_scan_probe(layouts, stack,
                                    jnp.asarray(kmin), jnp.asarray(kmax),
                                    lo, hi, 1, resident, True)
        assert np.array_equal(np.asarray(f_k), np.asarray(f_ref)), resident
        assert np.array_equal(np.asarray(t_k), np.asarray(t_ref)), resident


def test_kernel_rejects_bad_stacks(rng):
    layouts, states, kmin, kmax = _mixed_delta(rng)
    stack = build_run_stack(states)
    with pytest.raises(ValueError, match="one key domain"):
        store_scan_probe((layouts[0], basic_layout(24, 400, 12.0, delta=6)),
                         stack[:2], jnp.asarray(kmin[:2]),
                         jnp.asarray(kmax[:2]),
                         jnp.zeros(8, jnp.uint32), jnp.ones(8, jnp.uint32))
    with pytest.raises(ValueError, match="rowpad"):
        store_scan_probe(layouts, stack[:, :8], jnp.asarray(kmin),
                         jnp.asarray(kmax),
                         jnp.zeros(8, jnp.uint32), jnp.ones(8, jnp.uint32))


# ---------------------------------------------------------------------------
# dispatch shape: the whole scan plane is ONE kernel call per batch
# ---------------------------------------------------------------------------

def _count_prim(jaxpr, name) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                n += _count_prim(v.jaxpr, name)
            elif isinstance(v, (list, tuple)):
                n += sum(_count_prim(it.jaxpr, name) for it in v
                         if hasattr(it, "jaxpr"))
    return n


def test_fused_scan_is_one_pallas_call(rng):
    layouts, states, kmin, kmax = _mixed_delta(rng)
    stack = build_run_stack(states)
    lo, hi = _queries(rng, b=64)
    for resident in (True, False):     # VMEM-resident AND hbm tiers
        jaxpr = jax.make_jaxpr(
            lambda s, a, b: store_scan_probe(
                layouts, s, jnp.asarray(kmin), jnp.asarray(kmax),
                a, b, 64, resident, True))(stack, lo, hi)
        assert _count_prim(jaxpr.jaxpr, "pallas_call") == 1, (
            resident, jaxpr.pretty_print())


def test_store_kernel_path_is_one_pallas_call(rng):
    """Through the Store dispatch, a scan batch is still one kernel."""
    st = Store(StoreConfig(d=D, memtable_limit=300, level0_runs=3,
                           scan_backend="kernel"))
    for k in rng.integers(0, DMAX, 2000, dtype=np.uint64):
        st.put(int(k), 0)
    st.flush()
    st._refresh()
    layouts, stack, kmin_d, kmax_d, resident = st._kernel_inputs()
    lo = jnp.zeros(64, jnp.uint32)
    hi = jnp.full(64, 1 << 20, jnp.uint32)
    jaxpr = jax.make_jaxpr(
        lambda s, a, b: store_scan_probe(layouts, s, kmin_d, kmax_d,
                                         a, b, 256, resident, True))(stack, lo, hi)
    assert _count_prim(jaxpr.jaxpr, "pallas_call") == 1


# ---------------------------------------------------------------------------
# kernel-backed store vs XLA-backed store: same ops, same answers
# ---------------------------------------------------------------------------

def _fuzz_kernel_vs_xla(n_ops: int, seed: int):
    rng = np.random.default_rng(seed)
    def mk(backend):
        return Store(StoreConfig(
            d=D, memtable_limit=800, level0_runs=3, fanout=4,
            mutability="deletable", scan_backend=backend))
    st_k, st_x = mk("kernel"), mk("xla")
    chunk, scan_b = 2_000, 64
    for c0 in range(0, n_ops, chunk):
        ops = rng.random(chunk)
        ks = rng.integers(0, 1 << 32, chunk, dtype=np.uint64)
        for op, k in zip(ops, ks):
            k = int(k)
            if op < 0.85:
                st_k.put(k, k ^ 0x5CA7)
                st_x.put(k, k ^ 0x5CA7)
            else:
                dk = int(ks[rng.integers(0, chunk)])
                st_k.delete(dk)
                st_x.delete(dk)
        lo = rng.integers(0, (1 << 32) - (1 << 16), scan_b, dtype=np.uint64)
        hi = lo + rng.integers(1, 1 << 16, scan_b, dtype=np.uint64)
        hi[-4:] = np.uint64((1 << 32) + 5)     # exercise the domain clamp
        assert st_k.scan_many(lo, hi) == st_x.scan_many(lo, hi), c0
    # bit-identical verdicts leave bit-identical pruning stats behind
    assert st_k.stats.scan_filter_skips == st_x.stats.scan_filter_skips
    assert st_k.stats.scan_runs_touched == st_x.stats.scan_runs_touched
    assert st_k.stats.scans == st_x.stats.scans
    return st_k


def test_fuzz_kernel_vs_xla_store_deletable(rng):
    st = _fuzz_kernel_vs_xla(20_000, 0xC0FE)
    assert st.stats.flushes > 5        # the mix actually churned


@pytest.mark.slow
def test_fuzz_kernel_vs_xla_store_100k_ops():
    st = _fuzz_kernel_vs_xla(100_000, 0xC0FE)
    assert st.stats.compactions > 0
