"""Pallas kernels vs pure-jnp oracles (interpret mode): bit-identical
results across layout/shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BloomRF, FilterLayout, basic_layout
from repro.kernels import (FilterOps, insert_resident,
                           point_probe_partitioned, point_probe_resident,
                           range_probe_resident)
from repro.kernels import ref as kref


def _keys(rng, d, n):
    return rng.integers(0, (1 << d) - 1, n, dtype=np.uint64).astype(np.uint32)


@pytest.mark.parametrize("d,delta,n,bpk", [
    (32, 6, 2000, 12.0),
    (32, 7, 1000, 16.0),
    (24, 4, 3000, 10.0),
    (16, 2, 500, 14.0),
])
def test_insert_kernel_bit_identical(rng, d, delta, n, bpk):
    lay = basic_layout(d, n, bpk, delta=delta)
    keys = _keys(rng, d, n)
    st_ref = kref.insert_ref(lay, BloomRF(lay).init_state(),
                             jnp.asarray(keys))
    st_k = insert_resident(lay, BloomRF(lay).init_state(), jnp.asarray(keys),
                           128, True)
    assert (np.asarray(st_ref) == np.asarray(st_k)).all()


@pytest.mark.parametrize("tile", [64, 512])
@pytest.mark.parametrize("d,delta", [(32, 6), (32, 7), (20, 3)])
def test_point_probe_resident(rng, d, delta, tile):
    lay = basic_layout(d, 2000, 12.0, delta=delta)
    keys = _keys(rng, d, 2000)
    state = BloomRF(lay).build(jnp.asarray(keys, jnp.uint32))
    qs = np.concatenate([keys[:500], _keys(rng, d, 1500)])
    want = np.asarray(kref.point_ref(lay, state, jnp.asarray(qs)))
    got = np.asarray(point_probe_resident(lay, state, jnp.asarray(qs),
                                          tile, True))
    assert (want == got).all()
    assert got[:500].all()  # no false negatives through the kernel


@pytest.mark.parametrize("tile", [32, 512])
def test_point_probe_partitioned(rng, tile):
    lay = basic_layout(32, 5000, 14.0, delta=6)
    keys = _keys(rng, 32, 5000)
    state = BloomRF(lay).build(jnp.asarray(keys, jnp.uint32))
    qs = np.concatenate([keys[:300], _keys(rng, 32, 700)])
    want = np.asarray(kref.point_ref(lay, state, jnp.asarray(qs)))
    got = np.asarray(point_probe_partitioned(lay, state, jnp.asarray(qs),
                                             tile, True))
    assert (want == got).all()


@pytest.mark.parametrize("delta", [4, 6, 7])
def test_range_probe_kernel(rng, delta):
    lay = basic_layout(32, 2000, 14.0, delta=delta)
    keys = _keys(rng, 32, 2000)
    state = BloomRF(lay).build(jnp.asarray(keys, jnp.uint32))
    lo = _keys(rng, 32, 800)
    hi = lo + rng.integers(0, 1 << 10, 800).astype(np.uint32)
    hi = np.maximum(lo, hi)
    want = np.asarray(kref.range_ref(lay, state, jnp.asarray(lo),
                                     jnp.asarray(hi)))
    got = np.asarray(range_probe_resident(lay, state, jnp.asarray(lo),
                                          jnp.asarray(hi), 256, True))
    assert (want == got).all()


def test_filter_ops_dispatcher(rng):
    lay = basic_layout(32, 1000, 12.0, delta=6)
    ops = FilterOps(lay, interpret=True)
    keys = _keys(rng, 32, 1000)
    state = ops.insert(ops.init_state(), jnp.asarray(keys))
    assert np.asarray(ops.point(state, jnp.asarray(keys[:200]))).all()
    lo = jnp.asarray(keys[:100])
    hi = jnp.asarray(keys[:100] + np.uint32(7))
    assert np.asarray(ops.range(state, lo, hi)).all()


def test_kernel_rejects_64bit_domain():
    lay = basic_layout(64, 1000, 12.0, delta=7)
    with pytest.raises(ValueError):
        kref.check_kernel_layout(lay)


# ---------------------------------------------------------------------------
# kernel-vs-XLA parity across random layouts (multi-segment, replicas, any Δ)
# ---------------------------------------------------------------------------

def _random_kernel_layout(rng):
    """Random kernel-eligible layout: d <= 32, 2 segments, replicas, no exact."""
    d = int(rng.integers(16, 33))
    deltas, rem = [], d
    for _ in range(int(rng.integers(2, 5))):
        if rem < 1:
            break
        deltas.append(int(min(rng.integers(1, 8), rem)))
        rem -= deltas[-1]
    k = len(deltas)
    return FilterLayout(
        d=d, deltas=tuple(deltas),
        replicas=tuple(int(r) for r in rng.integers(1, 3, k)),
        seg_of_layer=tuple(int(s) for s in rng.integers(0, 2, k)),
        seg_bits=(8192, 4096), exact_seg=None,
        seed=int(rng.integers(1 << 30)))


@pytest.mark.parametrize("trial", range(6))
def test_range_kernel_parity_random_layouts(trial):
    trng = np.random.default_rng(0xC0FFEE + trial)
    lay = _random_kernel_layout(trng)
    f = BloomRF(lay)
    hi_excl = 1 << lay.d if lay.d < 64 else (1 << 63)
    keys = trng.integers(0, hi_excl, 600, dtype=np.uint64).astype(np.uint32)
    state = f.build(jnp.asarray(keys))
    lo = trng.integers(0, hi_excl, 400, dtype=np.uint64)
    hi = np.minimum(lo + trng.integers(0, 1 << min(lay.d - 1, 12), 400,
                                       dtype=np.uint64), hi_excl - 1)
    lo = lo.astype(np.uint32)
    hi = hi.astype(np.uint32)
    want = np.asarray(kref.range_ref(lay, state, jnp.asarray(lo),
                                     jnp.asarray(hi)))
    got = np.asarray(range_probe_resident(lay, state, jnp.asarray(lo),
                                          jnp.asarray(hi), 128, True))
    assert (want == got).all(), lay.describe()
    # same parity through the dispatcher: forced-XLA ops vs kernel ops
    ops_xla = FilterOps(lay, interpret=True, vmem_budget_u32=1)
    assert not ops_xla.resident
    via_xla = np.asarray(ops_xla.range(state, jnp.asarray(lo),
                                       jnp.asarray(hi)))
    assert (via_xla == got).all()


def test_exact_layout_range_kernel_raises():
    """Exact-layer layouts must be rejected by the kernel path, as documented
    in kernels/rangeprobe.py (bounded lane scan is XLA-only)."""
    from repro.core.tuning import advise

    lay = advise(16, 300, 16384, 64.0).layout
    assert lay.has_exact
    f = BloomRF(lay)
    state = f.build(jnp.asarray(np.arange(300, dtype=np.uint32)))
    lo = jnp.asarray(np.arange(10, dtype=np.uint32))
    with pytest.raises(ValueError, match="exact-layer"):
        range_probe_resident(lay, state, lo, lo, 128, True)


def test_exact_layout_ops_falls_back_to_xla(rng):
    """FilterOps.range on an exact-layer layout must silently take the XLA
    path and stay bit-identical to the core filter."""
    from repro.core.tuning import advise

    lay = advise(16, 300, 16384, 64.0).layout
    f = BloomRF(lay)
    keys = rng.integers(0, 1 << 16, 300, dtype=np.uint64).astype(np.uint32)
    ops = FilterOps(lay, interpret=True)
    state = ops.insert(ops.init_state(), jnp.asarray(keys))
    lo = rng.integers(0, 1 << 16, 500, dtype=np.uint64).astype(np.uint32)
    hi = np.minimum(lo + 64, (1 << 16) - 1).astype(np.uint32)
    got = np.asarray(ops.range(state, jnp.asarray(lo), jnp.asarray(hi)))
    want = np.asarray(f.range(state, jnp.asarray(lo), jnp.asarray(hi)))
    assert (want == got).all()
    # straddling ranges must all be positive (no false negatives)
    slo = np.maximum(keys.astype(np.int64) - 3, 0).astype(np.uint32)
    shi = np.minimum(keys.astype(np.int64) + 3, (1 << 16) - 1).astype(np.uint32)
    assert np.asarray(ops.range(state, jnp.asarray(slo),
                                jnp.asarray(shi))).all()
