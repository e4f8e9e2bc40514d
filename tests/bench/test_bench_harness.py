"""CPU tests of the chip benchmark's harness (``bench/``).

They drive the harness end to end at sizes a test run holds, on the
CPU, past its look for a chip: generators, references, the check that
decides ``correct`` (with each fault the cells can have planted under
the timed path, and with each cell's control), the registry, the trace
reduction, and the command's refusal to run without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry, roofline, stats, trace  # noqa: E402
from bench.traffic import Dataset, Traffic, spread  # noqa: E402

TINY = {
    "ycsb_e": {"config_overrides": {"recordcount": 6000}},
    "filter_range_u64": {
        "config_overrides": {"keys": 1 << 12, "spec": {"n": 1 << 12}},
        "traffic_overrides": {"clients": 256}},
}
SECONDS = 0.4


def _run(cell, seed=7, root=registry.ROOT, **kw):
    from bench.run import run_cell

    args = dict(TINY[cell])
    args.update(kw)
    return run_cell(cell, seed, SECONDS, False, root=root,
                    require_tpu=False, **args)


def _ycsb_data(n=5000, seed=1):
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, 1 << 32, n + 100, dtype=np.uint64))
    keys = rng.permutation(keys)[:n]
    return Dataset(keys, 32, 16)


def _steps(mix, seed, data, n):
    t = Traffic(mix, seed, data)
    return [t.next_step() for _ in range(n)]


# -- generators -----------------------------------------------------------------

@pytest.mark.parametrize("traffic", ["ycsb_e", "range_short"])
def test_generators_are_deterministic_per_seed(traffic):
    mix = registry.load_traffic(traffic)
    if traffic == "range_short":
        mix = dict(mix, clients=512)
    a = _steps(mix, 2**40 + 3, _ycsb_data(), 45)
    b = _steps(mix, 2**40 + 3, _ycsb_data(), 45)
    c = _steps(mix, 2**40 + 4, _ycsb_data(), 45)
    for x, y in zip(a, b):
        assert np.array_equal(x.scan_lo, y.scan_lo)
        assert np.array_equal(x.range_hi, y.range_hi)
        assert x.writes == y.writes
    assert any(not np.array_equal(x.scan_lo, z.scan_lo)
               or not np.array_equal(x.range_lo, z.range_lo)
               for x, z in zip(a, c))
    # every seed does the same work: same batch sizes, same widths
    assert [s.n_ops for s in a] and all(
        s.n_ops == mix["clients"] for s in a + c)
    size = lambda steps: sorted(
        len(s.scan_lo) * 1000 + len(s.writes) for s in steps)
    assert size(a[:40]) == size(c[:40])
    starts = lambda steps: sorted(np.concatenate(
        [s.scan_lo for s in steps]).tolist())
    assert starts(a[:40]) == starts(c[:40])
    w = lambda steps: sorted(np.concatenate(
        [s.range_hi - s.range_lo for s in steps]).tolist())
    assert w(a) == w(c)


def test_a_read_only_mix_replays_its_pool_in_turn():
    mix = dict(registry.load_traffic("range_short"), clients=64,
               replay_steps=3)
    steps = _steps(mix, 4, _ycsb_data(), 7)
    assert [s.origin for s in steps] == [0, 1, 2, 0, 1, 2, 0]
    assert [s.index for s in steps] == list(range(7))
    assert np.array_equal(steps[0].range_lo, steps[3].range_lo)
    with pytest.raises(ValueError):
        Traffic(dict(registry.load_traffic("ycsb_e"), replay_steps=2), 4,
                _ycsb_data())


def test_ycsb_e_mix_is_95_percent_scans_of_1_to_100_records():
    mix = registry.load_traffic("ycsb_e")
    steps = _steps(mix, 11, _ycsb_data(), 20)
    scans = sum(len(s.scan_lo) for s in steps)
    inserts = sum(len(s.writes) for s in steps)
    assert (scans, inserts) == (1216, 64)
    lens = np.concatenate([s.scan_len for s in steps])
    assert sorted(lens.tolist()) == spread(1216, 1, 100).tolist()
    assert Traffic(mix, 11, _ycsb_data()).batch_shapes() == {"scan": [60, 61]}


def test_ycsb_e_scan_bounds_return_exactly_len_loaded_records():
    data = _ycsb_data()
    steps = _steps(registry.load_traffic("ycsb_e"), 5, data, 40)
    ks = data.sorted_keys
    for s in steps:
        got = (np.searchsorted(ks, s.scan_hi, side="right")
               - np.searchsorted(ks, s.scan_lo, side="left"))
        room = len(ks) - np.searchsorted(ks, s.scan_lo)
        assert np.array_equal(got, np.minimum(s.scan_len, room))


def test_inserts_are_fresh_and_ranges_anchor_on_keys():
    data = _ycsb_data()
    steps = _steps(registry.load_traffic("ycsb_e"), 9, data, 60)
    new = [k for s in steps for k, _ in s.writes]
    assert len(set(new)) == len(new)
    assert not set(new) & set(data.sorted_keys.tolist())
    mix = dict(registry.load_traffic("range_short"), clients=1000)
    (step,) = _steps(mix, 9, data, 1)
    w = step.range_hi - step.range_lo + 1
    assert w.min() >= 16 and w.max() <= 1024
    holds = (np.searchsorted(data.sorted_keys, step.range_hi, side="right")
             > np.searchsorted(data.sorted_keys, step.range_lo))
    assert holds.sum() >= 500           # the stored-anchored half


def test_scrambled_zipfian_is_skewed_and_in_range():
    data = _ycsb_data(n=1000)
    t = Traffic(registry.load_traffic("ycsb_e"), 3, data)
    rec = t._records(np.random.default_rng(0), 20000)
    assert rec.min() >= 0 and rec.max() < 1000
    top = np.sort(np.bincount(rec, minlength=1000))[::-1]
    assert top[0] > 20 * np.median(top)


# -- references and the check ----------------------------------------------------

def test_kv_reference_last_write_wins():
    ref = registry.load_reference("kv_store")
    kv = ref.SortedKV(np.array([5, 1, 9], np.uint64), ["e", "a", "i"])
    kv.put(7, "g")
    kv.put(5, "E")
    assert kv.scan(1, 7) == [(1, "a"), (5, "E"), (7, "g")]
    assert kv.get(9) == "i" and kv.get(8) is None


def test_filter_reference_counts_false_negatives():
    ref = registry.load_reference("range_filter")
    keys = ref.SortedKeys(np.array([10, 20], np.uint64))
    truth = keys.range_truth(np.array([0, 11, 15]), np.array([10, 19, 25]))
    assert truth.tolist() == [True, False, True]
    r = ref.compare(truth, [False, True, True])
    assert r == {"false_negatives": 1, "negatives": 1, "false_positives": 1}
    # a verdict the batch did not return reads as "no"
    assert ref.compare(truth, [True])["false_negatives"] == 1


@pytest.mark.parametrize("cell", ["ycsb_e", "filter_range_u64"])
def test_references_agree_with_the_program_on_the_cpu(cell):
    r = _run(cell)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    exact = {k: c["value"] for k, c in r["checks"].items()
             if not k.endswith("_fpr")}
    assert exact and all(v == 0 for v in exact.values())
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    if cell == "filter_range_u64":
        # half the ranges hold no key, and most of those are pruned
        assert 0 <= r["checks"]["range_fpr"]["value"] < 0.1
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in registry.metrics_for(
        registry.load_benchmark(), cell, per_layer=False)}


@pytest.mark.parametrize("cell,mix", [
    ("ycsb_e", {"read": 0.5, "scan": 0.25, "update": 0.25}),
    ("filter_range_u64", {"point": 0.5, "range": 0.5}),
])
def test_every_operation_kind_is_checked_against_the_reference(cell, mix):
    r = _run(cell, traffic_overrides=dict(
        TINY[cell].get("traffic_overrides", {}), mix=mix,
        **({"request_distribution": {"kind": "uniform"}}
           if cell == "ycsb_e" else {})))
    assert r["correct"] is True and r["attempted"] > 0
    broken = _run(cell, traffic_overrides=dict(
        TINY[cell].get("traffic_overrides", {}), mix=mix),
        fault=(lambda s: _wrap(s.store, "get_many", _half))
        if cell == "ycsb_e" else
        (lambda s: _wrap(s.filter, "point", _flip)))
    assert broken["correct"] is False


def _wrap(obj, name, after):
    orig = getattr(obj, name)
    setattr(obj, name, lambda *a: after(orig(*a)))


def _half(answers):
    return answers[: len(answers) // 2]


def _alter_row(rows):
    for i, r in enumerate(rows):
        if r:
            k, v = r[0]
            rows[i] = [(k, bytes(b ^ 1 for b in v))] + r[1:]
            break
    return rows


def _flip(verdicts):
    v = np.array(verdicts)
    v[::3] = False
    return v


def _zero_state(system):
    import jax.numpy as jnp

    system.filter.state = jnp.zeros_like(system.filter.state)


# each fault goes through the program's public surface only
FAULTS = {
    # a write that returns with the store's state unchanged
    "ycsb_e/state_unchanged": lambda s: setattr(
        s.store, "put", lambda k, v: None),
    "ycsb_e/half_batch": lambda s: _wrap(s.store, "scan_many", _half),
    "ycsb_e/answer_altered": lambda s: _wrap(s.store, "scan_many",
                                             _alter_row),
    "filter_range_u64/state_unchanged": _zero_state,
    "filter_range_u64/half_batch": lambda s: _wrap(s.filter, "range", _half),
    "filter_range_u64/answer_altered": lambda s: _wrap(s.filter, "range",
                                                       _flip),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_timed_path_reads_not_correct(fault):
    cell = fault.split("/")[0]
    r = _run(cell, fault=FAULTS[fault])
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("cell,control,fails", [
    ("ycsb_e", "wal_off", "lost_writes"),
    ("ycsb_e", "recent_writes_hidden", "mismatched_answers"),
    ("filter_range_u64", "keys_dropped", "false_negatives"),
    ("filter_range_u64", "all_maybe", "range_fpr"),
])
def test_the_controls_read_not_correct(cell, control, fails):
    from bench.control import run_control

    r = run_control(cell, control, 13, SECONDS, require_tpu=False,
                    traffic_overrides=TINY[cell].get("traffic_overrides"),
                    config_overrides=TINY[cell]["config_overrides"])
    assert r["correct"] is False
    bad = {k for k, c in r["checks"].items() if c["value"] > c["limit"]}
    assert bad == {fails}


def test_every_control_is_found_by_its_system():
    """A control belongs to a system: every cell on it has it, and each
    control file names the compared number it has to fail."""
    bench = registry.load_benchmark()
    seen = set()
    for w in bench["workloads"]:
        system = registry.load_config(w["config"])["system"]
        for name in registry.controls_for(system):
            ctl = registry.load_control(system, name)
            assert ctl.FAILS in registry.load_config(w["config"])["limits"]
            seen.add(f"{system}.{name}")
    assert seen == {"kv_store.wal_off", "kv_store.recent_writes_hidden",
                    "range_filter.keys_dropped", "range_filter.all_maybe"}
    with pytest.raises(registry.RegistryError):
        registry.load_control("kv_store", "no_such_control")


# -- the registry ---------------------------------------------------------------

def test_registry_loads_every_named_file():
    bench = registry.load_benchmark()
    for c in bench["configs"]:
        cfg = registry.load_config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        registry.load_system(cfg["system"])
        registry.load_reference(cfg["reference"])
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"]
    for w in bench["workloads"]:
        registry.load_traffic(w["traffic"])
        assert registry.metrics_for(bench, w["name"], per_layer=True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(registry.load_reader(m["name"]).read)
    assert registry.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(registry.RegistryError):
        registry.load_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", ["", "../etc", "a b", "a/b", "é", "x" * 65])
def test_registry_rejects_bad_names(name):
    with pytest.raises(registry.RegistryError):
        registry.load_traffic(name)


@pytest.mark.parametrize("unit", ["tokens per second", "", "µs", "x" * 17])
def test_registry_rejects_bad_units(unit, tmp_path):
    with pytest.raises(registry.RegistryError):
        registry.check_unit(unit)
    bench = registry.load_benchmark()
    bench["end_to_end"][1]["unit"] = unit
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(registry.RegistryError):
        registry.load_benchmark(str(tmp_path))


def test_a_new_workload_file_is_picked_up(tmp_path):
    """A cell added as data (a traffic file and an entry) runs with no
    edit to the harness's code."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    mix = registry.load_traffic("ycsb_e")
    mix.update(mix={"scan": 0.5, "update": 0.5}, clients=8,
               request_distribution={"kind": "uniform"})
    (tmp_path / "bench" / "workloads" / "ycsb_a_like.json").write_text(
        json.dumps(mix))
    bench = registry.load_benchmark()
    bench["workloads"].append({"name": "ycsb_a_like", "config": "lsm_ycsb_1m",
                               "traffic": "ycsb_a_like", "chips": 1,
                               "why": "a test cell"})
    (ops,) = [m for m in bench["end_to_end"] if m["name"] == "ycsb_ops_s"]
    ops["workloads"].append("ycsb_a_like")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    from bench.run import run_cell

    r = run_cell("ycsb_a_like", 3, SECONDS, False, root=str(tmp_path),
                 require_tpu=False, config_overrides={"recordcount": 3000})
    assert r["correct"] is True
    assert set(r["metrics"]) == {"setup_s", "ycsb_ops_s"}


# -- the trace reduction ---------------------------------------------------------

def _fake_run(tr, **kw):
    import types

    ns = dict(trace=tr, step_s=np.array([0.01]), step_ops=np.array([1]),
              window_s=1.0, counters={}, compiles_in_window=0, peaks=None)
    ns.update(kw)
    return types.SimpleNamespace(**ns)


GATHER_OP = '%gather.1 = u32[16384,12] gather(u32[21875000] %a, s32[16384,12] %b)'


def test_trace_reduction_on_a_synthetic_trace():
    E = trace.Event
    tr = trace.Trace(
        {"/device:TPU:0": [E("fusion.1", 0.10, 0.20, "jit_range"),
                           E(GATHER_OP, 0.40, 0.30, "jit_range"),
                           E("fusion.2", 0.95, 0.10, "jit_other")]},
        [E("bench/step", 0.0, 0.5), E("bench/step", 0.5, 0.5),
         E("bench/range", 0.75, 0.2)])
    busy, win = trace.busy_seconds(tr)
    assert win == pytest.approx(1.0) and busy == pytest.approx(0.55)
    assert trace.op_seconds(tr, trace.PROGRAM["filter_probe"]) \
        == pytest.approx(0.5)
    assert trace.op_seconds(tr, r"^%gather") == pytest.approx(0.3)
    assert trace.op_seconds(tr, trace.PROGRAM["store_scan"]) == 0.0
    assert trace.step_device_busy(tr) == [
        (0.5, pytest.approx(0.3)), (0.5, pytest.approx(0.25))]
    b = trace.breakdown(tr)
    assert b["device_ops"][0] == ["jit_range:%gather.1",
                                  pytest.approx(0.3)]
    idle = dict(b["idle_gaps"])
    assert idle["bench/range"] == pytest.approx(0.2)
    assert idle["bench/step"] == pytest.approx(0.25)
    assert sum(idle.values()) == pytest.approx(0.45)
    assert trace.Trace.from_json(json.loads(json.dumps(tr.to_json()))) == tr
    idle_pct = registry.load_reader("device_idle_pct.filter").read(
        _fake_run(tr))
    assert idle_pct == pytest.approx(45.0)
    assert registry.load_reader("device_idle_pct.ycsb").read(
        _fake_run(tr)) == idle_pct
    host = registry.load_reader("store_host_ms.ycsb").read(_fake_run(tr))
    assert host == pytest.approx((0.2 + 0.25) / 2 * 1e3)
    assert registry.load_reader("filter_host_ms.filter").read(
        _fake_run(tr)) == host
    probe = registry.load_reader("probe_device_ms.filter").read(
        _fake_run(tr))
    assert probe == pytest.approx(0.5 / 2 * 1e3)


def test_flush_stall_counts_the_flush_and_the_builds_after_it():
    import types

    # a build before the flush is the warm-up's miss, not the flush's
    run = _fake_run(None, step_s=np.array([0.1, 0.5, 0.3, 0.1, 0.2, 0.4]),
                    window_s=1.8, step_builds=np.array([1, 0, 1, 0, 1, 0]),
                    system=types.SimpleNamespace(
                        step_flushes=[0, 1, 0, 0, 0, 0]))
    v = registry.load_reader("flush_stall_pct.ycsb").read(run)
    assert v == pytest.approx(100.0 * (0.5 + 0.3 + 0.2) / 1.8)


# what the recorded trace reduces to: (idle %, probe program ms per step)
RECORDED = {"ycsb_e": (99.48626088545367, 0.10473974999991587,
                       "store_probe_ms.ycsb", "device_idle_pct.ycsb"),
            "filter_range_u64": (23.26650304311397, 6.195947499999758,
                                 "probe_device_ms.filter",
                                 "device_idle_pct.filter")}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_trace_reduction_on_a_recorded_chip_trace(cell):
    """A trace recorded on a v5e (four steps of each cell) reduces to
    known per-layer numbers."""
    with open(os.path.join(os.path.dirname(__file__),
                           "trace_fixture.json")) as f:
        tr = trace.Trace.from_json(json.load(f)[cell])
    idle, probe_ms, probe_name, idle_name = RECORDED[cell]
    busy, win = trace.busy_seconds(tr)
    assert 0 < busy <= win and len(tr.steps()) == 4
    b = trace.breakdown(tr)
    assert b["device_ops"] and b["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(win - busy)
    run = _fake_run(tr, counters={"scans": 10, "scan_runs_touched": 5})
    read = lambda name: registry.load_reader(name).read(run)
    assert read(idle_name) == pytest.approx(idle, rel=1e-9)
    assert read(probe_name) == pytest.approx(probe_ms, rel=1e-9)
    bench = registry.load_benchmark()
    for m in registry.metrics_for(bench, cell, per_layer=True):
        if m["name"] in ("probe_roofline.filter", "flush_stall_pct.ycsb"):
            continue            # need the window's queries or steps
        v = read(m["name"])
        assert v is not None and v >= 0, m["name"]


def test_trace_of_a_cpu_run_loads(tmp_path):
    import jax
    import jax.numpy as jnp

    t = trace.Tracer(True, str(tmp_path))
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with t.window():
        for _ in range(2):
            with t.span("step"):
                f(jnp.ones(8)).block_until_ready()
    tr = t.load()
    assert len(tr.steps()) == 2 and tr.window()[1] > tr.window()[0]


def test_roofline_counts_the_words_a_probe_reads():
    layout = {"d": 32, "deltas": [7], "replicas": [1]}
    # one block: one 64-bit word (two lanes); two blocks: two words
    assert roofline.range_probe_bytes(layout, [0], [127]) == 8
    assert roofline.range_probe_bytes(layout, [100], [300]) == 16
    assert roofline.point_probe_bytes(layout, 3) == 24
    two = {"d": 32, "deltas": [4, 7], "replicas": [2, 1]}
    # layer 0: 8-bit words (one lane) x 2 replicas; layer 1: one 64-bit word
    assert roofline.range_probe_bytes(two, [0], [15]) == 4 * (2 + 2)


def test_rate_and_tail_count_every_operation():
    run = _fake_run(None, step_s=np.array([0.01, 0.03, 0.02]),
                    step_ops=np.array([10, 1, 89]), window_s=0.1)
    assert stats.ops_per_s(run) == pytest.approx(1000.0)
    assert stats.op_percentile_ms(run, 95) == pytest.approx(20.0)
    assert stats.op_percentile_ms(run, 99.5) == pytest.approx(30.0)


def test_window_report_shows_stalls_time_outside_steps_and_gc():
    import types

    run = _fake_run(None, step_s=np.array([0.01, 0.01, 0.05, 0.01, 0.2]),
                    window_s=0.3, counters={"flushes": 1, "compactions": 2})
    gc_clock = types.SimpleNamespace(count=[5, 1, 0], seconds=[0.01, 0.002,
                                                                0.0])
    lines = stats.window_report(run, gc_clock)
    assert lines[0] == "step ms p50 10.000 p95 170.000 p99 194.000 " \
        "max 200.000"
    assert lines[1].startswith("steps over 3x p50: 2, 0.250 s; slowest "
                               "(step, ms) [(4, 200.0), (2, 50.0)]")
    assert lines[2].startswith("outside steps: 0.020 s")
    assert lines[3] == "background work in the window: flushes 1, " \
        "compactions 2"
    assert lines[4] == "gc in the window: collections [5, 1, 0] by " \
        "generation, [0.01, 0.002, 0.0] s"


def test_the_roofline_counts_a_replayed_batch_each_time_it_ran():
    from bench.systems.range_filter import System

    system = System.__new__(System)
    lo = np.array([0, 1000], np.uint64)
    hi = np.array([127, 1100], np.uint64)
    ranges = (lo, hi, np.ones(2, bool))
    system.windows = [(ranges, None, 0), (ranges, None, 0),
                      (ranges, None, 0)]
    (times, arrays), = system.issued("range")
    assert times == 3 and arrays[0] is lo and arrays[1] is hi
    assert system.issued("point") == []
    layout = {"d": 64, "deltas": [7], "replicas": [1]}
    E = trace.Event
    tr = trace.Trace({"/device:TPU:0": [E("fusion", 0.1, 0.5, "jit_range")]},
                     [E("bench/step", 0.0, 1.0)])
    peaks = registry.load_peaks("TPU v5 lite")
    run = _fake_run(tr, peaks=peaks, system=system,
                    config={"layout": layout})
    nbytes = 3 * roofline.range_probe_bytes(layout, lo, hi)
    assert nbytes == 3 * (8 + 16)
    assert registry.load_reader("probe_roofline.filter").read(run) == \
        pytest.approx(100.0 * nbytes / 819e9 / 0.5)


# -- the command ----------------------------------------------------------------

def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_the_command_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "filter_range_u64",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_the_command_needs_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "ycsb_e",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and "{" not in p.stdout


def test_importing_the_harness_touches_no_jax_and_no_topology():
    code = ("import sys, bench.run, bench.control, bench.registry, "
            "bench.trace, bench.traffic, bench.stats, bench.roofline; "
            "print('jax' in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"
