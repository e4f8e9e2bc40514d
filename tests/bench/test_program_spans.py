"""CPU tests of the program-span reductions (``bench/spans.py``).

The fast coverage and idle split against ``bench/trace.py``'s own on the
recorded chip trace, every span reader on a synthetic trace, a traced
CPU run of each cell with the program's spans on (every span there,
nested as the program opens them, the counts matching the store's
counters), and a recorded chip trace with the program's spans.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry, spans, trace  # noqa: E402

E = trace.Event
HERE = os.path.dirname(__file__)


def _fixture(name, cell, cls=trace.Trace):
    with open(os.path.join(HERE, name)) as f:
        return cls.from_json(json.load(f)[cell])


def _run(tr, **kw):
    import types

    ns = dict(trace=tr, counters={}, compiles_in_window=0, peaks=None)
    ns.update(kw)
    return types.SimpleNamespace(**ns)


# -- the fast reductions read what bench/trace.py reads --------------------------

def test_coverage_equals_covered():
    rng = np.random.default_rng(3)
    starts = np.sort(rng.uniform(0, 10, 400))
    merged = trace.union(zip(starts, starts + rng.uniform(0, 0.05, 400)))
    cov = spans.Coverage(merged)
    lo = rng.uniform(-1, 11, 2000)
    hi = lo + rng.uniform(-0.5, 3, 2000)            # some hi < lo
    want = [trace.covered(merged, a, b) for a, b in zip(lo, hi)]
    np.testing.assert_allclose(cov(lo, hi), want, rtol=1e-9, atol=1e-12)
    empty = spans.Coverage(trace.union([]))
    assert empty(lo, hi).tolist() == [0.0] * len(lo)


@pytest.mark.parametrize("cell", ["filter_range_u64", "ycsb_e"])
def test_recorded_trace_reduces_as_before(cell):
    """The recorded chip trace without program spans: the idle split and
    the device busy time inside each step read the same through
    ``Coverage``."""
    tr = _fixture("trace_fixture.json", cell)
    want = dict(trace.breakdown(tr)["idle_gaps"])
    got = dict(spans.idle_gaps(tr))
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9), k
    busy, win = trace.busy_seconds(tr)
    assert sum(got.values()) == pytest.approx(win - busy, rel=1e-9)
    st = tr.steps()
    inside = spans.busy_inside(spans.device_coverage(tr),
                               [s.start for s in st], [s.end for s in st])
    np.testing.assert_allclose(
        inside, [b for _, b in trace.step_device_busy(tr)], rtol=1e-9)
    # a trace that also holds program spans reads the same in every
    # accepted reader
    with_program = spans.SpanTrace(tr.devices, tr.spans, [])
    run = _run(tr, counters={"scans": 10, "scan_runs_touched": 5})
    run2 = _run(with_program, counters=run.counters)
    for m in registry.metrics_for(registry.load_benchmark(), cell, True):
        if m["name"] in ("probe_roofline.filter", "flush_stall_pct.ycsb"):
            continue            # need the window's queries or steps
        read = registry.load_reader(m["name"]).read
        assert read(run2) == pytest.approx(read(run), rel=1e-9), m["name"]


# -- the readers ----------------------------------------------------------------

NINE = {m["name"]: m for m in spans.metric_entries()}


def test_span_metric_entries_are_benchmark_entries(tmp_path):
    """The entries of ``bench/spans.json`` pass the registry's checks as
    ``BENCHMARK.json`` per-layer metrics, each with a reader, and name
    no metric the benchmark has."""
    bench = registry.load_benchmark()
    have = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert len(NINE) == 9 and not set(NINE) & have
    bench["per_layer"] += list(NINE.values())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    registry.load_benchmark(str(tmp_path))
    for name, m in NINE.items():
        assert len(m["workloads"]) == 1
        assert callable(registry.load_reader(name).read)


def _synthetic():
    """Two 10 ms steps; program spans of every read name; the device busy
    2 ms inside the first prune span and 1 ms outside any span."""
    P = spans.PREFIX
    program = [E(P + "facade/encode", 0.001, 0.001),
               E(P + "probe/put", 0.002, 0.0005),
               E(P + "probe/dispatch", 0.0025, 0.0005),
               E(P + "probe/fetch", 0.004, 0.002),
               E(P + "store/scan/prune", 0.011, 0.004),
               E(P + "store/scan/memtable", 0.015, 0.001),
               E(P + "store/scan/memtable", 0.016, 0.002),
               E(P + "store/scan/runs", 0.018, 0.001),
               E(P + "wal/append", 0.0195, 0.0003)]
    dev = {"/device:TPU:0": [E("fusion.1", 0.012, 0.002, "jit__range_all"),
                             E("fusion.2", 0.0085, 0.001, "jit_range")]}
    return spans.SpanTrace(dev, [E("bench/step", 0.0, 0.01),
                                 E("bench/step", 0.01, 0.01)], program)


@pytest.mark.parametrize("name,want", [
    ("encode_ms.filter", 0.5), ("probe_put_ms.filter", 0.25),
    ("probe_dispatch_ms.filter", 0.25), ("probe_fetch_ms.filter", 1.0),
    ("scan_memtable_ms.ycsb", 1.5), ("scan_runs_ms.ycsb", 0.5),
    ("scan_prune_host_ms.ycsb", 1.0), ("wal_append_ms.ycsb", 0.15)])
def test_span_reader_reads_its_span(name, want):
    read = registry.load_reader(name).read
    assert read(_run(_synthetic())) == pytest.approx(want, rel=1e-9)
    assert read(_run(None)) is None                     # not traced
    tr = _synthetic()
    assert read(_run(trace.Trace(tr.devices, tr.spans))) is None
    other = spans.SpanTrace(tr.devices, tr.spans,
                            [E(spans.PREFIX + "unit/other", 0.001, 0.001)])
    assert read(_run(other)) == 0.0                     # spans, not this


def test_memtable_entries_per_scan_reads_the_counters():
    read = registry.load_reader("memtable_entries_per_scan.ycsb").read
    assert read(_run(None, counters={"scans": 4, "scan_mem_entries": 10})) \
        == 2.5
    assert read(_run(None, counters={"scans": 4})) is None
    assert read(_run(None, counters={"scans": 0,
                                     "scan_mem_entries": 0})) is None


def test_leaves_slow_steps_and_json_round_trip():
    tr = _synthetic()
    P = spans.PREFIX
    tr.program += [E(P + "facade/scan", 0.0105, 0.009)]
    lv = {s.name for s in spans.leaves(tr.program)}
    assert P + "facade/scan" not in lv and P + "store/scan/runs" in lv
    share = spans.leaf_share(tr)
    # host: 20 ms less 3 ms busy; leaves: 12.3 ms less the 2 ms busy
    # inside the prune span
    assert share["host_ms_per_step"] == pytest.approx(8.5)
    assert share["leaf_host_ms_per_step"] == pytest.approx(5.15)
    assert share["leaf_share_pct"] == pytest.approx(100 * 10.3 / 17)
    tr.spans.append(E("bench/step", 0.02, 0.1))
    slow = spans.slow_steps(tr)
    assert slow == [[2, 100.0, "bench/step", 100.0, 0.0]]
    assert spans.SpanTrace.from_json(
        json.loads(json.dumps(tr.to_json()))) == tr


# what the recorded trace with program spans (four steps of each cell on a
# v5e, ``python3 -m bench.spans``) reduces to, in ms per step
SPAN_RECORDED = {
    "filter_range_u64": {"encode_ms.filter": 0.014782500000001114,
                         "probe_put_ms.filter": 0.7215622500000018,
                         "probe_dispatch_ms.filter": 0.25180475000000047,
                         "probe_fetch_ms.filter": 0.5420147499999982},
    "ycsb_e": {"scan_memtable_ms.ycsb": 2.3295602500000254,
               "scan_runs_ms.ycsb": 24.320964999999998,
               "scan_prune_host_ms.ycsb": 2.1344680000000764,
               "wal_append_ms.ycsb": 0.22940249999999807},
}


@pytest.mark.parametrize("cell", sorted(SPAN_RECORDED))
def test_span_readers_on_a_recorded_chip_trace(cell):
    tr = _fixture("span_trace_fixture.json", cell, spans.SpanTrace)
    assert len(tr.steps()) == 4 and tr.program
    run = _run(tr)
    want = SPAN_RECORDED[cell]
    # every span metric of the cell (the counter one reads the window's
    # counters, not the trace)
    assert set(want) == {n for n, m in NINE.items() if cell in m["workloads"]
                         and m["source"] == "program_span"}
    for name, v in want.items():
        assert registry.load_reader(name).read(run) == pytest.approx(
            v, rel=1e-9), name
    busy, win = trace.busy_seconds(tr)
    idle = spans.idle_gaps(tr, top=100)
    assert sum(v for _, v in idle) == pytest.approx(win - busy, rel=1e-9)
    assert idle[0][0].startswith(spans.PREFIX)
    assert spans.leaf_share(tr)["leaf_share_pct"] > 85


# -- a traced run on the CPU -------------------------------------------------------

PARENTS = {
    "ycsb_e": {
        "facade/scan": "bench/scan_many", "facade/encode": "facade/scan",
        "facade/decode": "facade/scan", "store/scan": "facade/scan",
        "store/scan/prune": "store/scan",
        "store/refresh": "store/scan/prune",
        "store/scan/dispatch": "store/scan/prune",
        "store/scan/fetch": "store/scan/prune",
        "store/scan/memtable": "store/scan", "store/scan/runs": "store/scan",
        "facade/put": "bench/put", "wal/append": "facade/put",
        "store/flush": "facade/put"},
    "filter_range_u64": {
        "facade/range": "bench/range", "facade/encode": "facade/range",
        "probe/put": "facade/range", "probe/dispatch": "facade/range",
        "probe/wait": "facade/range", "probe/fetch": "facade/range"},
}
OPTIONAL = {"store/compact": "facade/put",
            "compaction/merge_runs": "store/compact",
            "compaction/merge_filters": "store/compact"}
# a store whose memtable is one entry short of its flush as the window
# opens, so the window flushes and the next scan refreshes the stack
TINY = {
    "ycsb_e": {"config_overrides": {"recordcount": 5999,
                                    "spec": {"memtable_limit": 2000}}},
    "filter_range_u64": {
        "config_overrides": {"keys": 1 << 12, "spec": {"n": 1 << 12}},
        "traffic_overrides": {"clients": 256}},
}


def _parents(tr):
    """Each program span with the innermost span (any) that holds it."""
    out, stack = [], []
    for s in sorted(tr.spans + tr.program, key=lambda s: (s.start, -s.dur)):
        while stack and not (stack[-1].start <= s.start
                             and s.end <= stack[-1].end):
            stack.pop()
        if s.name.startswith(spans.PREFIX):
            out.append((s, stack[-1] if stack else None))
        stack.append(s)
    return out


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_spans_of_a_cpu_run(cell):
    result, summary, run = spans.profile(cell, 7, 0.4, require_tpu=False,
                                         **TINY[cell])
    assert result["correct"] is True
    tr = run.trace
    P = spans.PREFIX
    want = {**PARENTS[cell], **OPTIONAL}
    seen = set()
    steps = tr.steps()
    for s, parent in _parents(tr):
        name = s.name[len(P):]
        seen.add(name)
        assert name in want, name
        assert parent is not None and parent.name in (want[name],
                                                      P + want[name]), name
        assert any(st.start <= s.start and s.end <= st.end for st in steps)
    assert set(PARENTS[cell]) <= seen
    count = summary["span_counts"]
    if cell == "ycsb_e":
        c = run.counters
        assert count["store/scan/memtable"] == c["scans"] > 0
        assert count["store/scan/runs"] == c["scans"]
        # the memtable a scan walks, rebuilt from the window's steps: 1,999
        # entries as it opens, one more per insert, empty after a flush
        size, walked = 5999 % 2000, 0
        for scans, _, writes in run.system.windows:
            walked += size * (len(scans[0]) if scans else 0)
            for _ in writes:
                size = 0 if size + 1 >= 2000 else size + 1
        assert c["scan_mem_entries"] == walked
        # every touched run that was no false positive gave a row or more
        assert c["scan_rows_sliced"] >= c["scan_runs_touched"] \
            - c["scan_fp_reads"] > 0
    else:
        chunk = run.system.filter.spec.chunk
        chunks = sum(-(-len(w[0][0]) // chunk) for w in run.system.windows)
        assert count["probe/dispatch"] == chunks == len(steps)
    for name, m in NINE.items():
        if cell in m["workloads"]:
            assert summary["metrics"][name]["value"] >= 0, name


def test_span_tracer_loads_what_trace_load_loads(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.obs import trace as obs_trace

    t = spans.SpanTracer(True, str(tmp_path))
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with t.window():
        assert obs_trace.enabled()
        for _ in range(2):
            with t.span("step"), obs_trace.span("unit/op"):
                f(jnp.ones(8)).block_until_ready()
    assert not obs_trace.enabled()
    path = [os.path.join(d, n) for d, _, ns in os.walk(t.dir) for n in ns
            if n.endswith(".xplane.pb")][0]
    plain = trace.load(path)
    tr = t.load()
    assert tr.devices == plain.devices and tr.spans == plain.spans
    assert [s.name for s in tr.program] == [spans.PREFIX + "unit/op"] * 2
    assert t.load_s > 0


def test_importing_bench_spans_touches_no_jax():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", "import sys, bench.spans; "
                        "print('jax' in sys.modules)"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"
