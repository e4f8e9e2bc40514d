"""Observability plane (src/repro/obs/, DESIGN.md §15).

Covers the three layers and their two hard contracts:

* registry units — device-scalar counter accumulation, histogram
  percentile semantics, family registration/flattening, reset;
* span tracing — null singleton when off, latency histogram when the
  metrics plane is on too, and spans alone (the profiled run's switch)
  touching no sampler, family or histogram;
* FPR telemetry — both invalidation modes (insert-stream and ground
  truth), the re-probe, and the workload reservoir;
* the **zero-overhead contract**: with observability ENABLED the jaxpr
  of a stacked range probe still contains exactly ONE gather, the fused
  store scan exactly ONE ``pallas_call``, and the jaxpr text is
  bit-for-bit identical to the disabled run and to a spans-only run;
* durable ``StoreStats`` round-trips through ``Store.snapshot()`` /
  ``restore()``, and the real ``gates.toml`` obs gates evaluate a
  ``bloomrf-metrics/v1`` document end to end.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from benchmarks import check_gates as cg
from repro import obs
from repro.core import basic_layout, stacked_probe
from repro.kernels.store_scan import store_scan_probe
from repro.obs import FprSampler, export_snapshot
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.store import Store, StoreConfig
from repro.store.store import StoreStats


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Isolated obs state: fresh registry, metrics and spans off.

    The registry and both switches are process globals — tests must not
    leak counters or the enabled state into each other (or into the
    rest of the suite, which pins obs-off jaxprs elsewhere)."""
    monkeypatch.setattr(obs_metrics, "_REGISTRY", obs_metrics.MetricsRegistry())
    monkeypatch.setattr(obs_metrics, "_ENABLED", False)
    monkeypatch.setattr(obs_trace, "_ENABLED", False)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_accumulates_host_and_device_scalars():
    c = obs_metrics.registry().counter("unit/c")
    c.add(3)
    c.add(jnp.asarray(4, jnp.int32))     # device scalar: no sync until read
    c.add(jnp.asarray(5, jnp.int32))
    assert c.value() == 12
    assert isinstance(c.value(), int)


def test_gauge_set_and_read():
    g = obs_metrics.registry().gauge("unit/g")
    g.set(2.5)
    assert g.value() == 2.5
    g.set(jnp.asarray(7.0))
    assert g.value() == 7.0


def test_histogram_percentiles_are_covering_bucket_edges():
    h = obs_metrics.registry().histogram("unit/h", buckets=(1.0, 10.0, 100.0))
    h.observe(5.0)                        # lands in (1, 10]
    assert h.percentile(0.5) == 10.0
    h.observe_many(np.asarray([0.5, 50.0, 50.0, 1e6]))   # last overflows
    snap = h.snapshot_value()
    assert set(snap) == {"count", "mean", "p50", "p99"}
    assert snap["count"] == 5
    assert snap["p50"] == 100.0           # 3rd of 5 → (10, 100]
    assert snap["p99"] == 100.0           # overflow clamps to the top edge


def test_registry_rejects_kind_conflicts():
    reg = obs_metrics.registry()
    reg.counter("unit/x")
    with pytest.raises(TypeError):
        reg.gauge("unit/x")
    with pytest.raises(TypeError):
        reg.histogram("unit/x")


def test_families_flatten_suffix_and_prune():
    reg = obs_metrics.registry()
    assert reg.register_family("fam", lambda: {"a": 1, "b": 2.5}) == "fam"
    assert reg.register_family("fam", lambda: {"a": 9}) == "fam#2"
    reg.register_family("gone", lambda: None)     # dead owner → pruned
    snap = reg.snapshot()
    assert snap["fam/a"] == 1 and snap["fam/b"] == 2.5
    assert snap["fam#2/a"] == 9
    assert not any(k.startswith("gone") for k in snap)


def test_reset_zeroes_metrics_but_keeps_families():
    reg = obs_metrics.registry()
    reg.counter("unit/c").add(5)
    reg.register_family("fam", lambda: {"a": 1})
    reg.reset()
    snap = reg.snapshot()
    assert snap["unit/c"] == 0
    assert snap["fam/a"] == 1             # families survive a reset


def test_export_snapshot_schema_and_extra():
    obs_metrics.registry().counter("unit/c").add(1)
    doc = export_snapshot(extra={"obs/overhead_ratio": 1.01})
    assert doc["schema"] == "bloomrf-metrics/v1"
    assert doc["metrics"]["unit/c"] == 1
    assert doc["metrics"]["obs/overhead_ratio"] == 1.01


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_span_is_null_singleton_when_disabled():
    assert obs_trace.span("unit/op") is obs_trace.NULL_SPAN
    with obs_trace.span("unit/op"):
        pass
    assert "obs/latency/unit/op" not in obs_metrics.registry().snapshot()


def test_span_feeds_latency_histogram_and_jsonl_sink():
    obs.enable()
    with obs_trace.span("unit/op"):
        pass
    with obs_trace.span("unit/op"):
        pass
    snap = obs_metrics.registry().snapshot()
    assert snap["obs/latency/unit/op"]["count"] == 2
    assert snap["obs/latency/unit/op"]["mean"] >= 0.0


def test_spans_and_metrics_have_separate_switches():
    obs_metrics.enable()                  # metrics alone: spans stay off
    assert obs_trace.span("unit/op") is obs_trace.NULL_SPAN
    obs_metrics.disable()
    obs_trace.enable()                    # spans alone: a bare annotation
    sp = obs_trace.span("unit/op")
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp:
        pass
    assert obs_metrics.registry().snapshot() == {}
    obs.disable()
    assert not obs_trace.enabled() and not obs_metrics.enabled()


def _spans_only_answers(on: bool, rng_seed: int):
    """A filter's range/point answers and a store's scans, with spans on
    alone or everything off."""
    (obs_trace.enable if on else obs_trace.disable)()
    rng = np.random.default_rng(rng_seed)
    keys = rng.integers(0, 1 << 32, 2000, dtype=np.uint64)
    f = repro.open_filter(repro.FilterSpec(dtype="u64", n=2000,
                                           bits_per_key=12, backend="xla"))
    f.insert(keys)
    lo = np.concatenate([keys[:64], rng.integers(0, 1 << 40, 64,
                                                 dtype=np.uint64)])
    hi = lo + np.uint64(100)
    st = repro.open_filter(repro.FilterSpec(
        dtype="u64", placement="store", memtable_limit=300, level0_runs=2,
        backend="xla"))
    for k in keys[:1000].tolist():
        st.put(k, k)
    return (f.range(lo, hi), f.point(lo), st.scan_many(lo, hi), f, st)


def test_spans_alone_build_no_sampler_family_or_histogram():
    on = _spans_only_answers(True, 11)
    snap = obs_metrics.registry().snapshot()
    assert snap == {}                     # no family, no histogram
    assert on[3]._fpr is None and on[4]._fpr is None
    assert on[4].stats.scans == 128
    off = _spans_only_answers(False, 11)
    np.testing.assert_array_equal(on[0], off[0])
    np.testing.assert_array_equal(on[1], off[1])
    assert on[2] == off[2]


def _latency_counts() -> dict:
    return {k[len("obs/latency/"):]: v["count"]
            for k, v in obs_metrics.registry().snapshot().items()
            if k.startswith("obs/latency/")}


@pytest.mark.parametrize("placement", ["single", "tenant"])
def test_facade_probe_spans_hold_the_encode_on_every_handle(placement):
    """`facade/range` and `facade/point` time the same work on every
    filter handle: the encode is a `facade/encode` child of each."""
    obs.enable()
    keys = np.arange(40, dtype=np.uint64) * 7 + 3
    if placement == "tenant":
        h = repro.open_filter(repro.FilterSpec(
            dtype="u32", n=64, placement="tenant", tenants=2, shards=2))
        h.insert(1, keys)
        assert h.range(1, keys, keys + 1).all() and h.point(1, keys).all()
    else:
        h = repro.open_filter(repro.FilterSpec(dtype="u32", n=64,
                                               backend="xla"))
        h.insert(keys)
        assert h.range(keys, keys + 1).all() and h.point(keys).all()
    counts = _latency_counts()
    assert counts["facade/encode"] == 2
    assert counts["facade/range"] == counts["facade/point"] == 1


def test_store_point_probes_carry_no_scan_plane_spans():
    """The pruning call's dispatch and fetch spans belong to scans; a
    batch of gets goes through the same call without them."""
    obs.enable()
    st = repro.open_filter(repro.FilterSpec(
        dtype="u64", placement="store", memtable_limit=64, level0_runs=2,
        backend="xla"))
    for k in range(200):
        st.put(k * 3, k)
    before = _latency_counts()
    assert st.get_many(np.arange(0, 30, 3, dtype=np.uint64))[1] == 1
    counts = _latency_counts()
    grew = {k for k, n in counts.items() if n != before.get(k, 0)}
    assert grew <= {"facade/get", "store/get", "store/refresh"}
    st.scan_many(np.asarray([0, 30], np.uint64), np.asarray([9, 90], np.uint64))
    counts = _latency_counts()
    assert counts["store/scan/dispatch"] == counts["store/scan/fetch"] == 1


# ---------------------------------------------------------------------------
# FPR telemetry
# ---------------------------------------------------------------------------

def test_fpr_sampler_rejects_bad_domain():
    with pytest.raises(ValueError):
        FprSampler(0)
    with pytest.raises(ValueError):
        FprSampler(65)


def test_fpr_insert_stream_invalidation():
    s = FprSampler(16, n_keys=64, n_ranges=64, range_len=16, seed=1)
    assert s.live_points().size == 64
    s.observe_insert(s.keys[:10])          # kill the first ten candidates
    assert s.live_points().size == 54
    # a key inside a candidate range makes that range non-absent
    s.observe_insert(np.asarray([s.lo[0]], np.uint64))
    lo, _ = s.live_ranges()
    assert s.lo[0] not in lo


def test_fpr_mark_present_replaces_insert_stream_state():
    s = FprSampler(16, n_keys=64, n_ranges=64, seed=2)
    s.observe_insert(s.keys)               # insert stream kills everything
    assert s.live_points().size == 0
    s.mark_present(np.asarray([], np.uint64))   # ground truth: store is empty
    assert s.live_points().size == 64      # replaced, not merged
    s.mark_present(s.keys[:5])
    assert s.live_points().size == 59


def test_fpr_sample_reprobes_surviving_candidates():
    s = FprSampler(16, n_keys=32, n_ranges=32, seed=3)
    out = s.sample(point_probe=lambda k: np.ones(k.size, bool),
                   range_probe=lambda lo, hi: np.zeros(lo.size, bool))
    assert out["point_candidates"] == 32 and out["point_fpr"] == 1.0
    assert out["range_candidates"] == 32 and out["range_fpr"] == 0.0
    s2 = FprSampler(16, n_keys=32, n_ranges=32, seed=3)
    s2.mark_present(s2.keys)               # nothing left to re-probe
    out2 = s2.sample(point_probe=lambda k: np.ones(k.size, bool))
    assert out2["point_candidates"] == 0 and "point_fpr" not in out2


def test_fpr_workload_reservoir_and_histogram():
    obs_metrics.enable()
    s = FprSampler(32, seed=4, reservoir_cap=8)
    lo = np.arange(20, dtype=np.uint64)
    s.observe_ranges(lo, lo + np.uint64(255))   # length 256 → log2 = 8
    assert s.workload_seen == 20
    assert len(s.workload_sample()) == 8        # capped, Algorithm R
    snap = obs_metrics.registry().snapshot()
    h = snap["obs/workload/range_log2"]
    assert h["count"] == 20 and h["p50"] == 8.0


# ---------------------------------------------------------------------------
# zero-overhead contract: obs ON must not change jaxprs
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


def _stacked_case(rng):
    layouts = [basic_layout(32, 1000, 14.0, delta=6),
               basic_layout(32, 4000, 14.0, delta=4)]
    bases = (0, layouts[0].total_u32)
    flat = jnp.zeros(sum(lay.total_u32 for lay in layouts), jnp.uint32)
    return stacked_probe(tuple(layouts), bases), flat


def test_stacked_probe_one_gather_with_obs_enabled(rng):
    obs.enable()
    sp, flat = _stacked_case(rng)
    lo = jnp.zeros(64, jnp.uint32)
    hi = jnp.full(64, 9999, jnp.uint32)
    jaxpr = jax.make_jaxpr(sp._range_all)(flat, lo, hi)
    assert _count_prim(jaxpr.jaxpr, "gather") == 1, jaxpr.pretty_print()
    jaxpr_p = jax.make_jaxpr(sp._point_all)(flat, lo)
    assert _count_prim(jaxpr_p.jaxpr, "gather") == 1


def test_jaxpr_text_identical_obs_on_vs_off(rng):
    """jax.named_scope adds NO equations: the traces must be bit-equal,
    with everything on and with the spans on alone."""
    sp, flat = _stacked_case(rng)
    lo = jnp.zeros(64, jnp.uint32)
    hi = jnp.full(64, 9999, jnp.uint32)
    obs.disable()
    off = str(jax.make_jaxpr(sp._range_all)(flat, lo, hi))
    obs.enable()
    on = str(jax.make_jaxpr(sp._range_all)(flat, lo, hi))
    assert on == off
    obs.disable()
    obs_trace.enable()
    spans_only = str(jax.make_jaxpr(sp._range_all)(flat, lo, hi))
    assert spans_only == off


def test_store_scan_one_pallas_call_with_obs_enabled(rng):
    obs.enable()
    st = Store(StoreConfig(d=32, memtable_limit=300, level0_runs=3,
                           scan_backend="kernel"))
    st.register_obs()
    for k in rng.integers(0, (1 << 32) - 1, 1200, dtype=np.uint64):
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
    # the dispatch odometer ticks on the host, outside the traced fn
    st.scan_probe_device(lo, hi)
    snap = obs_metrics.registry().snapshot()
    assert snap["store/scan_probe_batches"] == 1


# ---------------------------------------------------------------------------
# StoreStats: registered family + durable round-trip
# ---------------------------------------------------------------------------

def test_store_stats_snapshot_and_reset():
    s = StoreStats()
    s.puts, s.retunes = 7, 2
    assert s.snapshot()["puts"] == 7
    assert s.durable_snapshot() == {
        **{name: 0 for name in StoreStats.DURABLE},
        "puts": 7, "retunes": 2}
    s.reset()
    assert s.puts == 0 and s.retunes == 0


def test_store_register_obs_family(rng):
    obs_metrics.enable()
    st = Store(StoreConfig(d=32, memtable_limit=100))
    st.register_obs()
    for k in range(5):
        st.put(k, k)
    snap = obs_metrics.registry().snapshot()
    assert snap["store/puts"] == 5


def test_durable_stats_survive_snapshot_restore(rng):
    src = Store(StoreConfig(d=32, memtable_limit=50, level0_runs=2,
                            mutability="deletable"))
    for k in rng.integers(0, 1 << 20, 400, dtype=np.uint64):
        src.put(int(k), 1)
    src.delete(int(rng.integers(1 << 20)))
    src.stats.retunes = 3                 # write-path history, durable
    src.stats.gets = 99                   # read-path: process-local only
    snap = src.snapshot()
    dst = Store.restore(snap)
    for name in StoreStats.DURABLE:
        assert getattr(dst.stats, name) == getattr(src.stats, name), name
    assert dst.stats.gets == 0            # local counters do NOT travel


def test_restore_rejects_malformed_stats(rng):
    src = Store(StoreConfig(d=32, memtable_limit=50))
    src.put(1, 1)
    good = src.snapshot()
    for bad in ("nope", {"puts": -1}, {"not_a_counter": 1}, {"puts": "x"}):
        snap = dict(good)
        snap["stats"] = bad
        with pytest.raises(ValueError, match="stats"):
            Store.restore(snap)


def test_durable_stats_survive_checkpoint_reopen(tmp_path, rng):
    cfg = StoreConfig(d=32, memtable_limit=60, level0_runs=2,
                      durability="wal", wal_dir=str(tmp_path))
    st = Store(cfg)
    for k in rng.integers(0, 1 << 20, 150, dtype=np.uint64):
        st.put(int(k), 7)
    st.checkpoint()
    st.put(123, 9)                        # lands in the WAL tail
    puts_before = st.stats.puts
    st.close()
    re = Store.open(str(tmp_path))
    # checkpointed history + the replayed tail are both counted
    assert re.stats.puts == puts_before
    assert re.stats.wal_replayed >= 1


# ---------------------------------------------------------------------------
# gates: the committed obs gates evaluate a metrics document end to end
# ---------------------------------------------------------------------------

def _metrics_doc(**over):
    m = {"obs/fpr/observed": 0.02, "obs/fpr/model": 0.05,
         "obs/overhead_ratio": 1.01,
         "obs/latency/facade/scan": {"count": 3, "mean": 5.0,
                                     "p50": 4.0, "p99": 16.0}}
    m.update(over)
    return {"schema": "bloomrf-metrics/v1", "metrics": m}


def test_obs_gates_pass_on_healthy_metrics(tmp_path):
    path = tmp_path / "m.json"
    path.write_text(json.dumps(_metrics_doc()))
    msgs = cg.run_check(cg.load_config(), only={"obs_metrics"},
                        overrides={"obs_metrics": str(path)})
    assert len(msgs) == 3


@pytest.mark.parametrize("over", [
    {"obs/fpr/observed": 0.50},           # >2x model + slack
    {"obs/overhead_ratio": 1.20},         # obs plane entered the dispatch
    {"obs/latency/facade/scan": {"count": 0}},   # spans stopped feeding
])
def test_obs_gates_fail_on_bad_metrics(tmp_path, over):
    path = tmp_path / "m.json"
    path.write_text(json.dumps(_metrics_doc(**over)))
    with pytest.raises(cg.GateError):
        cg.run_check(cg.load_config(), only={"obs_metrics"},
                     overrides={"obs_metrics": str(path)})


def test_unknown_metrics_schema_refused(tmp_path):
    path = tmp_path / "m.json"
    doc = _metrics_doc()
    doc["schema"] = "bloomrf-metrics/v999"
    path.write_text(json.dumps(doc))
    with pytest.raises(cg.InputError):
        cg.run_check(cg.load_config(), only={"obs_metrics"},
                     overrides={"obs_metrics": str(path)})
