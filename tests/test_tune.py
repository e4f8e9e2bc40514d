"""Workload-adaptive tuner (§16): observe → fit → solve → retune.

Covers the whole loop: advisor input validation, the FprSampler workload
reservoir (exact Algorithm R — determinism and unbiasedness), the
``bloomrf-workload/v1`` model and its serde contract, the sample-driven
cost model against the engine's own probe accounting, solver hysteresis,
the AdaptiveTuner decision cache, and the store/facade wiring: retunes
fire at class-graduating compactions, the tuned store answers exactly
like its static twin (ZERO false negatives), snapshots carry the
workload model, and the one-gather / one-``pallas_call`` probe-plane
invariants survive a retuned (mixed-layout) run stack.
"""
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import basic_layout
from repro.core.engine import _filter_for_layout
from repro.core.tuning import advise
from repro.obs.fpr import LOG2_BUCKETS, SAMPLE_FIELDS, FprSampler
from repro.store import Store, StoreConfig, scan_plane
from repro.tune import (AdaptiveTuner, Hysteresis, WorkloadModel,
                        candidate_layouts, cross_check, fit_workload,
                        score_layout, solve)
from repro.tune.cost import words_per_range_query
from repro.tune.workload import N_RANGE_BUCKETS, SCHEMA, range_log2_bucket

from conftest import brute_force_range_truth


# ---------------------------------------------------------------------------
# advisor input validation (satellite: core/tuning.py::advise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs,needle", [
    (dict(d=0, n=10, m_bits=1000, R=4.0), "d must be"),
    (dict(d=65, n=10, m_bits=1000, R=4.0), "d must be"),
    (dict(d=-3, n=10, m_bits=1000, R=4.0), "d must be"),
    (dict(d=32, n=0, m_bits=1000, R=4.0), "n must be"),
    (dict(d=32, n=-1, m_bits=1000, R=4.0), "n must be"),
    (dict(d=32, n=10, m_bits=0, R=4.0), "m_bits must be"),
    (dict(d=32, n=10, m_bits=-64, R=4.0), "m_bits must be"),
    (dict(d=32, n=10, m_bits=1000, R=0.5), "R must be"),
    (dict(d=32, n=10, m_bits=1000, R=float("nan")), "R must be"),
])
def test_advise_rejects_bad_inputs(kwargs, needle):
    with pytest.raises(ValueError, match=needle):
        advise(**kwargs)


def test_advise_infeasible_budget_is_a_clear_error():
    # budget too small for ANY exact level: actionable message, not a
    # StopIteration from the internal candidate sweep
    with pytest.raises(ValueError, match="no feasible"):
        advise(d=32, n=10, m_bits=1, R=16.0)
    # feasible exact level but no room for the hashed segments
    with pytest.raises(ValueError, match="no feasible"):
        advise(d=1, n=4, m_bits=64, R=2.0)


def test_advise_boundary_d1_and_d64():
    lo = advise(d=1, n=4, m_bits=4096, R=2.0)
    assert lo.layout.d == 1 and lo.exact_level == 1
    hi = advise(d=64, n=10_000, m_bits=400_000, R=2.0 ** 20)
    assert hi.layout.d == 64
    assert 0.0 <= hi.fpr_point <= hi.fpr_w and np.isfinite(hi.fpr_w)
    assert sum(hi.layout.deltas) + hi.exact_level <= 64 + hi.exact_level


# ---------------------------------------------------------------------------
# FprSampler workload reservoir: determinism + unbiasedness + schema
# ---------------------------------------------------------------------------

def _feed(sampler, lo, hi, batch):
    for s in range(0, len(lo), batch):
        sampler.observe_ranges(lo[s:s + batch], hi[s:s + batch])


def test_sampler_workload_is_deterministic_and_batch_invariant():
    """Same seed + same stream => identical sample, however batched.

    The vectorized Algorithm R draws exactly one uniform per stream item
    (the fill phase draws none), so the RNG stream position — and hence
    the reservoir — cannot depend on how callers batch their scans."""
    rng = np.random.default_rng(7)
    lo = rng.integers(0, 1 << 30, 5000, dtype=np.uint64)
    hi = lo + rng.integers(1, 1 << 12, 5000, dtype=np.uint64)
    samples = []
    for batch in (5000, 137, 1):
        s = FprSampler(32, seed=0xFEED, reservoir_cap=256)
        _feed(s, lo, hi, batch)
        samples.append((s.workload_sample(), s.workload_seen,
                        s.range_log2_counts.copy()))
    for other in samples[1:]:
        assert other[0] == samples[0][0]
        assert other[1] == samples[0][1]
        np.testing.assert_array_equal(other[2], samples[0][2])


def test_sampler_reservoir_is_unbiased_chi_square():
    """Every position of a 1e5-item stream is equally likely to survive:
    decile occupancy of the reservoir passes a chi-square test (df=9,
    alpha=1e-3 critical value 27.88). Fixed seed => deterministic."""
    n, cap = 100_000, 1024
    s = FprSampler(32, seed=0xC41, reservoir_cap=cap)
    pos = np.arange(n, dtype=np.uint64)       # lo encodes stream position
    _feed(s, pos, pos, 4096)
    assert s.workload_seen == n
    kept = np.asarray([a for a, _ in s.workload_sample()], np.int64)
    assert kept.size == cap
    obs = np.bincount(kept // (n // 10), minlength=10)
    exp = cap / 10.0
    chi2 = float(((obs - exp) ** 2 / exp).sum())
    assert chi2 < 27.88, f"reservoir decile bias: chi2={chi2:.1f}, {obs}"


def test_sampler_sample_schema_is_pinned(rng):
    """sample() keys are exactly the pinned SAMPLE_FIELDS contract that
    the workload fit consumes by name."""
    s = FprSampler(16, n_keys=64, n_ranges=64, seed=3)
    base = s.sample()
    assert set(base) == set(SAMPLE_FIELDS[:3])
    full = s.sample(point_probe=lambda ks: np.ones(len(ks), bool),
                    range_probe=lambda lo, hi: np.zeros(len(lo), bool))
    assert set(full) == set(SAMPLE_FIELDS)
    assert full["point_fpr"] == 1.0 and full["range_fpr"] == 0.0


def test_sampler_range_histogram_buckets_dyadically():
    s = FprSampler(32, seed=5)
    lo = np.zeros(3, np.uint64)
    hi = np.asarray([0, 255, 256], np.uint64)     # lengths 1, 256, 257
    s.observe_ranges(lo, hi)
    np.testing.assert_array_equal(range_log2_bucket([1, 256, 257]),
                                  [0, 8, 9])
    assert s.range_log2_counts[0] == 1
    assert s.range_log2_counts[8] == 1
    assert s.range_log2_counts[9] == 1
    assert s.range_log2_counts.sum() == 3


def test_sampler_preload_roundtrip_and_validation():
    src = FprSampler(24, seed=11, reservoir_cap=128)
    lo = np.arange(500, dtype=np.uint64)
    src.observe_ranges(lo, lo + np.uint64(31))
    dst = FprSampler(24, seed=99, reservoir_cap=128)
    dst.preload_workload(src.workload_sample(), src.workload_seen,
                         src.range_log2_counts)
    assert dst.workload_sample() == src.workload_sample()
    assert dst.workload_seen == src.workload_seen
    np.testing.assert_array_equal(dst.range_log2_counts,
                                  src.range_log2_counts)
    with pytest.raises(ValueError, match="lo > hi"):
        dst.preload_workload([(5, 2)], 1)
    with pytest.raises(ValueError, match="log2_counts"):
        dst.preload_workload([(1, 2)], 1, np.ones(7))
    with pytest.raises(ValueError, match="log2_counts"):
        dst.preload_workload([(1, 2)], 1, -np.ones(len(LOG2_BUCKETS)))


# ---------------------------------------------------------------------------
# WorkloadModel: fit, derived views, serde contract
# ---------------------------------------------------------------------------

def _sampled_workload(seed=21, n=400, length=64, d=32):
    rng = np.random.default_rng(seed)
    s = FprSampler(d, seed=seed)
    lo = rng.integers(0, 1 << 24, n, dtype=np.uint64)
    s.observe_ranges(lo, lo + np.uint64(length - 1))
    keys = rng.integers(0, 1 << d, 2000, dtype=np.uint64)
    return fit_workload(d, sampler=s, keys=keys,
                        observed={"range_fpr": 0.02}, n_points=100)


def test_workload_fit_and_derived_views():
    wl = _sampled_workload()
    assert wl.n_ranges == 400 and wl.n_points == 100
    assert wl.point_frac() == pytest.approx(0.2)
    w = wl.range_weights()
    assert w.shape == (N_RANGE_BUCKETS,) and w.sum() == pytest.approx(1.0)
    assert w[6] == pytest.approx(1.0)             # every range length 64
    # clustered keys (all in the low 2^24 of a 2^32 domain... keys here
    # are uniform over 2^32, so C stays ~1); a point mass must raise C
    assert 1.0 <= wl.c_factor <= 1.5
    spike = WorkloadModel(
        d=32, range_log2=np.zeros(N_RANGE_BUCKETS), n_ranges=0, n_points=0,
        key_density=np.eye(64)[0], observed={}, reservoir=())
    assert spike.c_factor == 1.5                  # capped, never unbounded
    # empty workload: weights collapse onto the default R budget
    w0 = spike.range_weights(default_log2=8)
    assert w0[8] == 1.0 and w0.sum() == 1.0


def test_workload_rescaled_shifts_range_lengths():
    wl = _sampled_workload(length=256)            # all mass in bucket 8
    down = wl.rescaled(-2)                        # shard-local: len / 4
    assert down.range_log2[6] == wl.range_log2[8]
    assert down.range_log2.sum() == wl.range_log2.sum()
    assert wl.rescaled(0) is wl


def test_workload_serde_roundtrip_through_real_bytes():
    wl = _sampled_workload()
    enc = pickle.loads(pickle.dumps(wl.to_dict()))
    assert enc["schema"] == SCHEMA
    back = WorkloadModel.from_dict(enc)
    assert back.d == wl.d
    assert back.n_ranges == wl.n_ranges and back.n_points == wl.n_points
    np.testing.assert_array_equal(back.range_log2, wl.range_log2)
    np.testing.assert_array_equal(back.key_density, wl.key_density)
    assert back.observed == wl.observed
    assert back.reservoir == wl.reservoir


@pytest.mark.parametrize("mutate,needle", [
    (lambda e: e.pop("schema"), "schema"),
    (lambda e: e.update(schema="bloomrf-workload/v9"), "schema"),
    (lambda e: e.update(d=0), "d must be"),
    (lambda e: e.update(d="32"), "d must be"),
    (lambda e: e.update(range_log2=[1.0] * 7), "range_log2"),
    (lambda e: e["range_log2"].__setitem__(0, -1.0), "range_log2"),
    (lambda e: e.update(key_density=None), "key_density"),
    (lambda e: e.update(n_ranges=-1), "n_ranges"),
    (lambda e: e.update(n_points=True), "n_points"),
    (lambda e: e.update(observed={"range_fpr": "high"}), "observed"),
    (lambda e: e.update(reservoir=[[5, 2]]), "reservoir"),
    (lambda e: e.update(reservoir=[[-1, 2]]), "reservoir"),
])
def test_workload_from_dict_rejects_malformed(mutate, needle):
    enc = _sampled_workload().to_dict()
    mutate(enc)
    with pytest.raises(ValueError, match=needle):
        WorkloadModel.from_dict(enc)


def test_workload_from_dict_rejects_non_dict():
    with pytest.raises(ValueError, match="dict"):
        WorkloadModel.from_dict([1, 2, 3])


# ---------------------------------------------------------------------------
# cost model: engine-true probe accounting, workload-shaped FPR
# ---------------------------------------------------------------------------

def test_cost_words_match_engine_accounting():
    for delta in (2, 4, 6):
        lay = basic_layout(24, 4000, 12.0, delta=delta)
        assert words_per_range_query(lay) == float(
            _filter_for_layout(lay).engine.range_word_loads)


def test_cost_longer_ranges_never_get_cheaper():
    """fpr_range is an integral over max(fpr[0..l]) — pushing workload
    mass to longer ranges can only raise the predicted range FPR."""
    lay = basic_layout(32, 8000, 12.0, delta=6)
    short = _sampled_workload(length=16)
    long = _sampled_workload(length=1 << 14)
    a = score_layout(lay, 8000, short)
    b = score_layout(lay, 8000, long)
    assert b.fpr_range >= a.fpr_range
    assert 0.0 <= a.fpr_point <= a.fpr_mix <= 1.0
    assert a.objective >= a.fpr_mix          # word cost is a penalty


def test_cost_rejects_bad_n_keys():
    with pytest.raises(ValueError, match="n_keys"):
        score_layout(basic_layout(24, 100, 12.0), 0, _sampled_workload())


def test_cross_check_reports_clipped_calibration():
    wl = _sampled_workload()                     # observed range_fpr 0.02
    lay = basic_layout(32, 8000, 12.0, delta=6)
    out = cross_check(lay, 8000, wl)
    assert set(out) >= {"predicted_range_fpr", "observed_range_fpr",
                        "calibration"}
    assert out["observed_range_fpr"] == 0.02
    assert 0.25 <= out["calibration"] <= 4.0
    blind = _sampled_workload()
    blind.observed.clear()
    assert cross_check(lay, 8000, blind)["calibration"] is None


# ---------------------------------------------------------------------------
# solver: equal-budget candidates, hysteresis
# ---------------------------------------------------------------------------

def test_candidates_are_hashed_single_segment_at_equal_budget():
    cur = basic_layout(32, 20_000, 14.0, delta=6)
    cands = candidate_layouts(cur, 20_000)
    assert len(cands) >= 4
    for lay in cands:
        assert lay != cur
        assert lay.d == cur.d
        assert lay.exact_seg is None             # probe-plane stackable
        assert len(lay.seg_bits) == 1
        # equal bits per key: never buys a win with more memory (only the
        # 64-bit word round-up / tiny-geometry floor may pad upward)
        assert lay.seg_bits[0] <= max(cur.seg_bits[0] + 64,
                                      2 * (1 << 6) + 64)
        assert sum(lay.deltas) <= lay.d


def test_hysteresis_validation():
    with pytest.raises(ValueError, match="min_win"):
        Hysteresis(min_win=1.0)
    with pytest.raises(ValueError, match="min_win"):
        Hysteresis(min_win=-0.1)
    with pytest.raises(ValueError):
        Hysteresis(cooldown=-1)


def test_solve_short_range_workload_shrinks_deltas():
    """A scan workload of short ranges on a coarse-δ ladder must retune
    to finer deltas (fewer wasted dyadic levels => lower predicted FPR)."""
    cur = basic_layout(32, 20_000, 14.0, delta=6)
    wl = _sampled_workload(length=8, n=500)
    dec = solve(wl, 20_000, cur)
    assert dec.changed and dec.win >= 0.10
    assert max(dec.layout.deltas) < max(cur.deltas)
    assert dec.best.objective < dec.baseline.objective
    assert "->" in dec.reason


def test_solve_hysteresis_blocks_small_wins_and_cold_workloads():
    cur = basic_layout(32, 20_000, 14.0, delta=6)
    wl = _sampled_workload(length=8, n=500)
    held = solve(wl, 20_000, cur, Hysteresis(min_win=0.9999))
    assert not held.changed and held.layout is cur
    assert "min_win" in held.reason
    cold = _sampled_workload(n=8)                # below min_ranges=64
    gate = solve(cold, 20_000, cur)
    assert not gate.changed and gate.n_candidates == 0
    assert "insufficient workload" in gate.reason


# ---------------------------------------------------------------------------
# AdaptiveTuner: decision cache, cooldown, events, serde
# ---------------------------------------------------------------------------

def _hot_tuner(length=8, n=500, d=32):
    t = AdaptiveTuner(d, hysteresis=Hysteresis(cooldown=2))
    rng = np.random.default_rng(31)
    lo = rng.integers(0, 1 << 24, n, dtype=np.uint64)
    t.observe_scan(lo, lo + np.uint64(length - 1))
    return t


def test_tuner_retune_event_and_flush_cache():
    t = _hot_tuner()
    ladder = basic_layout(32, 20_000, 14.0, delta=6)
    tuned = t.advise_layout(ladder, 20_000)
    assert tuned != ladder and t.retunes == 1
    ev = t.events[0]
    assert ev["class_deltas"] == list(ladder.deltas)
    assert ev["tuned_deltas"] == list(tuned.deltas)
    assert ev["predicted_fpr_mix"] < ev["baseline_fpr_mix"]
    # flushes get the standing decision without a solve
    assert t.cached_layout(ladder) == tuned
    # an unconsulted capacity class has no standing decision
    assert t.cached_layout(basic_layout(32, 500, 14.0, delta=6)) is None
    # a second consultation reuses the cache: no duplicate event
    assert t.advise_layout(ladder, 20_000) == tuned
    assert t.retunes == 1 and len(t.events) == 1
    rep = t.report()
    assert rep["retunes"] == 1 and rep["workload"]["schema"] == SCHEMA
    assert str(ladder.deltas) in rep["decisions"]


def test_tuner_cooldown_limits_resolves(monkeypatch):
    import repro.tune.retune as retune_mod

    t = _hot_tuner()
    ladder = basic_layout(32, 20_000, 14.0, delta=6)
    calls = []
    real = retune_mod.solve
    monkeypatch.setattr(retune_mod, "solve",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    for _ in range(5):
        t.advise_layout(ladder, 20_000)
    # cooldown=2: solve at consultation 1, cached for 2, solve again at 4
    assert len(calls) == 2


def test_tuner_min_ranges_gate_and_observed_fold():
    t = AdaptiveTuner(32)
    ladder = basic_layout(32, 20_000, 14.0, delta=6)
    assert t.advise_layout(ladder, 20_000) == ladder     # cold: no solve
    assert t.retunes == 0 and t.cached_layout(ladder) is None
    t.record_observed({"range_fpr": 0.05, "point_candidates": 3})
    t.observe_points(40)
    assert t.observed == {"range_fpr": 0.05}
    assert t.workload().n_points == 40


def test_tuner_serde_roundtrip_and_validation():
    t = _hot_tuner()
    t.observe_points(7)
    t.record_observed({"range_fpr": 0.01})
    enc = pickle.loads(pickle.dumps(t.to_dict()))
    back = AdaptiveTuner(32)
    back.load(enc)
    assert back.sampler.workload_seen == t.sampler.workload_seen
    assert back.sampler.workload_sample() == t.sampler.workload_sample()
    assert back.points_seen == 7 and back.observed == {"range_fpr": 0.01}
    with pytest.raises(ValueError, match="d=32"):
        AdaptiveTuner(24).load(enc)
    with pytest.raises(ValueError, match="schema"):
        back.load({"schema": "nope"})


# ---------------------------------------------------------------------------
# store wiring: retunes fire at compaction, twins agree, snapshots carry
# the workload (tentpole acceptance)
# ---------------------------------------------------------------------------

def _skewed_ops(seed, n_keys=12_000, n_scans=384, scan_len=256):
    """A clustered key set + short-scan workload: exactly the shape the
    coarse static ladder overprices and the tuner wins on."""
    rng = np.random.default_rng(seed)
    keys = ((rng.random(n_keys) ** 4) * (1 << 31)).astype(np.uint64)
    keys += rng.integers(0, 1 << 22, n_keys, dtype=np.uint64)
    keys = np.minimum(keys, (1 << 32) - 1)
    starts = keys[rng.integers(0, n_keys, n_scans)] + np.uint64(1)
    starts = np.minimum(starts, (1 << 32) - np.uint64(scan_len))
    return keys, starts, starts + np.uint64(scan_len - 1)


def _drive(st, keys, slo, shi):
    half = len(keys) // 2
    for i, k in enumerate(keys[:half]):
        st.put(int(k), i)
    st.flush()
    scans = []
    for s in range(0, len(slo), 64):
        scans.extend(st.scan_many(slo[s:s + 64], shi[s:s + 64]))
    for i, k in enumerate(keys[half:]):
        st.put(int(k), half + i)
    st.flush()
    return scans


def _absent_range_fpr(st, keys, seed, n=2000, length=256):
    rng = np.random.default_rng(seed)
    lo = rng.integers(1 << 30, (1 << 31), n, dtype=np.uint64)
    hi = lo + np.uint64(length - 1)
    empty = ~brute_force_range_truth(keys, lo, hi)
    fence, filt = st.probe_runs(lo[empty], hi[empty])
    return float((fence & filt).any(axis=1).mean())


def _twin_cfg(tuning):
    return StoreConfig(d=32, memtable_limit=800, level0_runs=3, fanout=4,
                       bits_per_key=14.0, tuning=tuning)


def test_store_adaptive_retunes_and_beats_static_twin():
    """The §16 acceptance loop: identical op streams through a static and
    an adaptive store — the adaptive one must (a) retune at least once
    via the compaction re-insert path, (b) answer every query identically
    (zero false negatives, identical scans), and (c) leak strictly fewer
    false positives on the short-scan workload it observed."""
    keys, slo, shi = _skewed_ops(0xA5EED)
    st_s = Store(_twin_cfg("static"))
    st_a = Store(_twin_cfg("adaptive"))
    scans_s = _drive(st_s, keys, slo, shi)
    scans_a = _drive(st_a, keys, slo, shi)
    assert st_a.stats.retunes >= 1, "compaction never landed a retune"
    assert st_a._tuner.retunes >= 1 and st_a._tuner.events
    assert st_s.stats.retunes == 0
    # zero false negatives + twin equality
    assert scans_a == scans_s
    qs = np.unique(keys)
    assert st_a.get_many(qs) == st_s.get_many(qs)
    # the live stack really holds tuner-chosen layouts, not the ladder's
    assert any(r.layout != st_a.class_layout(len(r))
               for r in st_a.live_runs())
    # strictly fewer false positives at equal bits per key
    fpr_a = _absent_range_fpr(st_a, keys, 0xF00)
    fpr_s = _absent_range_fpr(st_s, keys, 0xF00)
    assert fpr_a < fpr_s, (fpr_a, fpr_s)


def test_store_adaptive_composes_with_deletable_churn():
    """Retuning must not break the deletable lane's purge/promote
    machinery: mixed put/delete churn with live scans, zero FN."""
    rng = np.random.default_rng(0xDE1E7E)
    st = Store(StoreConfig(d=24, memtable_limit=256, level0_runs=2,
                           fanout=4, bits_per_key=14.0,
                           mutability="deletable", tuning="adaptive"))
    space = 1 << 24
    model = {}
    for i in range(12_000):
        if model and rng.random() < 0.4:
            k = int(next(iter(model)))
            st.delete(k)
            del model[k]
        else:
            k = int(rng.integers(0, space))
            st.put(k, i)
            model[k] = i
        if i % 500 == 499:                       # live scan workload
            lo = rng.integers(0, space - 64, 32, dtype=np.uint64)
            st.scan_many(lo, lo + np.uint64(63))
    st.flush()
    live = np.fromiter(model.keys(), np.uint64, len(model))
    assert st.get_many(live) == [model[int(k)] for k in live], \
        "adaptive+deletable churn produced a false negative"
    assert st.stats.promote_merges + st.stats.purge_rebuilds > 0
    assert st._tuner.sampler.workload_seen > 0
    # scans still return exactly the live surviving rows
    lo = live[:16]
    for got, k in zip(st.scan_many(lo, lo), lo):
        assert (int(k), model[int(k)]) in got


@pytest.mark.slow
def test_store_adaptive_twin_fuzz_slow_1e5():
    """Headline §16 fuzz: 1e5 mixed ops through adaptive+deletable vs
    static+deletable twins — same answers, zero FN, retunes fired."""
    def run(tuning):
        rng = np.random.default_rng(0x57EED)
        st = Store(StoreConfig(d=24, memtable_limit=1024, level0_runs=2,
                               fanout=4, bits_per_key=14.0,
                               mutability="deletable", tuning=tuning))
        model = {}
        for i in range(100_000):
            if model and rng.random() < 0.4:
                k = int(next(iter(model)))
                st.delete(k)
                del model[k]
            else:
                k = int(rng.integers(0, 1 << 24))
                st.put(k, i)
                model[k] = i
            if i % 1000 == 999:
                lo = rng.integers(0, (1 << 24) - 256, 64, dtype=np.uint64)
                st.scan_many(lo, lo + np.uint64(255))
        st.flush()
        return st, model

    st_a, model_a = run("adaptive")
    st_s, model_s = run("static")
    assert model_a == model_s                    # identical op streams
    live = np.fromiter(model_a.keys(), np.uint64, len(model_a))
    got_a, got_s = st_a.get_many(live), st_s.get_many(live)
    assert got_a == [model_a[int(k)] for k in live]
    assert got_a == got_s
    assert st_a.stats.retunes >= 1


def test_store_config_validates_tuning():
    with pytest.raises(ValueError, match="tuning"):
        StoreConfig(d=24, tuning="bogus")
    with pytest.raises(ValueError, match="adaptive"):
        StoreConfig(d=24, tuning="adaptive", filter_backend="none")


def test_store_snapshot_carries_workload_model():
    keys, slo, shi = _skewed_ops(0xBEEF, n_keys=4000, n_scans=128)
    st = Store(_twin_cfg("adaptive"))
    _drive(st, keys, slo, shi)
    snap = st.snapshot()
    assert snap["workload"]["schema"] == SCHEMA
    st2 = Store.restore(pickle.loads(pickle.dumps(snap)))
    assert st2._tuner is not None
    assert st2._tuner.sampler.workload_seen == \
        st._tuner.sampler.workload_seen
    assert st2.stats.retunes == st.stats.retunes
    qs = np.unique(keys)[:500]
    assert st2.get_many(qs) == st.get_many(qs)
    # static stores snapshot without a workload payload
    assert "workload" not in Store(_twin_cfg("static")).snapshot()
    # corrupt payloads fail loudly at restore
    bad = pickle.loads(pickle.dumps(snap))
    bad["workload"]["range_log2"] = [1.0, 2.0]
    with pytest.raises(ValueError, match="workload"):
        Store.restore(bad)


def test_retuned_stack_keeps_probe_plane_invariants():
    """The §16 acceptance invariant: a retuned (mixed-layout) run stack
    still probes as ONE fused gather and scans as ONE pallas_call."""
    from test_engine import _count_gathers
    from test_store_scan_kernel import _count_prim
    from repro.kernels.store_scan import store_scan_probe

    keys, slo, shi = _skewed_ops(0x1AB, n_keys=8000, n_scans=256)
    st = Store(StoreConfig(d=32, memtable_limit=800, level0_runs=3,
                           fanout=4, bits_per_key=14.0, tuning="adaptive",
                           scan_backend="kernel"))
    _drive(st, keys, slo, shi)
    assert st.stats.retunes >= 1
    st._refresh()
    assert any(r.layout != st.class_layout(len(r))
               for r in st.live_runs())
    # one gather through the stacked point/range probe plane
    lo = jnp.asarray(np.arange(64), jnp.uint32)
    jx = jax.make_jaxpr(
        lambda stack, a: scan_plane.range_all(stack, a, a))(
        st._run_stack(), lo)
    assert _count_gathers(jx.jaxpr) == 1
    # one pallas_call through the scan megakernel
    layouts, stack, kmin_d, kmax_d, resident = st._kernel_inputs()
    jk = jax.make_jaxpr(
        lambda s, a, b: store_scan_probe(layouts, s, kmin_d, kmax_d,
                                         a, b, 256, resident, True))(
        stack, lo, jnp.asarray(np.arange(64) + (1 << 20), jnp.uint32))
    assert _count_prim(jk.jaxpr, "pallas_call") == 1


# ---------------------------------------------------------------------------
# facade: FilterSpec plumbing, retune_report, tenant retune-on-promote
# ---------------------------------------------------------------------------

def test_facade_adaptive_spec_validation():
    from repro.api import FilterSpec

    with pytest.raises(ValueError, match="adaptive"):
        FilterSpec(dtype="u32", tuning="adaptive")            # single
    with pytest.raises(ValueError, match="adaptive"):
        FilterSpec(dtype="u32", placement="bank", tuning="adaptive")
    FilterSpec(dtype="u32", placement="store", tuning="adaptive")
    FilterSpec(dtype="u32", placement="tenant", tenants=2,
               tuning="adaptive")


def test_facade_store_retune_report():
    from repro.api import FilterSpec, open_filter

    f = open_filter(FilterSpec(dtype="u32", placement="store",
                               tuning="adaptive", memtable_limit=500,
                               level0_runs=2))
    keys, slo, shi = _skewed_ops(0xFACADE % (1 << 31), n_keys=6000,
                                 n_scans=192)
    half = len(keys) // 2
    for i, k in enumerate(keys[:half]):
        f.put(int(k), i)
    f.flush()
    for s in range(0, len(slo), 64):
        f.scan_many(slo[s:s + 64], shi[s:s + 64])
    for i, k in enumerate(keys[half:]):
        f.put(int(k), half + i)
    f.flush()
    rep = f.retune_report()
    assert rep["tuning"] == "adaptive" and rep["retunes"] >= 1
    assert rep["events"] and rep["workload"]["schema"] == SCHEMA
    assert rep["decisions"]
    # observed_fpr feeds the model's live cross-check
    out = f.observed_fpr()
    rep2 = f.retune_report()
    if "range_fpr" in out:
        cc = rep2["cross_check"]
        assert cc["observed_range_fpr"] == out["range_fpr"]
        assert cc["calibration"] is None or 0.25 <= cc["calibration"] <= 4.0
    # zero FN through the facade after retuning
    assert all(v is not None for v in f.get_many(np.unique(keys)[:500]))
    # static stores report a stub, not an error
    g = open_filter(FilterSpec(dtype="u32", placement="store"))
    assert g.retune_report() == {"tuning": "auto", "retunes": 0,
                                 "events": []}


def test_facade_tenant_adaptive_grow_is_advised(rng):
    from repro.api import FilterSpec, open_filter

    f = open_filter(FilterSpec(dtype="u32", n=1024, placement="tenant",
                               tenants=3, shards=2, tuning="adaptive"))
    tenants = rng.integers(0, 3, 600).astype(np.uint32)
    keys = rng.integers(0, 1 << 32, 600, dtype=np.uint64)
    f.insert(tenants, keys)
    lo = rng.integers(0, (1 << 32) - 256, 200, dtype=np.uint64)
    f.range(tenants[:200], lo, lo + np.uint64(255))
    f.grow()                                     # factor advised, not fixed
    rep = f.retune_report()
    assert rep["tuning"] == "adaptive"
    assert rep["workload_seen"] == 200
    assert len(rep["promotions"]) == 1
    ev = rep["promotions"][0]
    assert ev["factor"] >= 2 and ev["reports"]
    assert rep["workload"]["schema"] == SCHEMA
    # zero FN after the advised promotion
    assert np.asarray(f.point(tenants, keys)).all()
    assert np.asarray(f.range(tenants, keys, keys)).all()


def test_facade_tenant_adaptive_composes_with_ttl(rng):
    from repro.api import FilterSpec, open_filter

    f = open_filter(FilterSpec(dtype="u32", n=512, placement="tenant",
                               tenants=2, mutability="ttl", generations=2,
                               tuning="adaptive"))
    tenants = rng.integers(0, 2, 300).astype(np.uint32)
    keys = rng.integers(0, 1 << 32, 300, dtype=np.uint64)
    f.insert(tenants, keys)
    f.range(tenants, np.maximum(keys, 8) - np.uint64(8), keys)
    f.advance_generation()
    f.grow()                                     # advised + TTL lanes
    assert np.asarray(f.point(tenants, keys)).all()
    f.advance_generation()
    f.advance_generation()
    assert np.asarray(f.point(tenants, keys)).mean() < 0.05


def test_tenant_bank_advise_promotion_scales_with_target():
    from repro.dist import TenantFilterBank

    bank = TenantFilterBank(d=32, n_tenants=2, n_shards=2,
                            n_keys_per_tenant=1024, _warn=False)
    wl = _sampled_workload(length=64, n=300)
    f_small, rep_small = bank.advise_promotion(wl, n_target=2048)
    f_big, rep_big = bank.advise_promotion(wl, n_target=8192)
    assert f_small >= 2 and f_big >= f_small
    assert 2 in rep_small and f_big in rep_big
    assert all(r.fpr_mix >= 0 for r in rep_small.values())
    # a target beyond every candidate factor is an error, not a silent
    # under-provision
    with pytest.raises(ValueError):
        bank.advise_promotion(wl, n_target=1 << 30)
    with pytest.raises(ValueError, match="n_current"):
        bank.advise_promotion(wl, n_current=0)
