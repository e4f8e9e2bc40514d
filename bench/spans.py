"""The program's own spans in a traced window, and what they split.

The program names its host spans ``bloomrf/<name>`` (``repro.obs.trace``:
``jax.profiler`` annotations, so they share the device trace's clock
with the benchmark's ``bench/...`` spans).  They are off unless switched
on, and ``bench.trace.load`` keeps only the benchmark's own.  This
module holds what reads them:

* ``SpanTracer``: the harness's tracer with the program's spans switched
  on for the window and kept by ``load`` (a ``SpanTrace``);
* ``Coverage``: device busy time inside any interval from prefix sums
  over the merged busy intervals, so that a window of ~10^5-10^6 spans
  reduces in seconds (``bench.trace.covered`` scans every interval);
* the reductions the readers share: a span's time per step, with or
  without the device's busy time inside it (``span_ms_per_step``); the
  share of the steps' host time that leaf spans cover; the idle gaps by
  the innermost span, program spans included; the slow steps and what
  held them;
* the per-layer metrics that read them (``bench/spans.json``, one reader
  each under ``bench/metrics/``).

Run one cell with the program's spans on::

    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s>

It runs ``bench.run.run_cell`` traced, with ``SpanTracer`` in place of the
harness's tracer, prints the run's own result line, and then one JSON
line with the metrics of ``bench/spans.json``, the time of every program
span per step, the span counts beside the store's counters, the leaf
spans' share of the host time, the idle gaps and the slow steps.

Importing this module imports no JAX.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import json
import os
import shutil
import sys
import time
import types

import numpy as np

if __package__ in (None, ""):       # run as a script: bench/spans.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import registry, trace                       # noqa: E402
from bench.trace import Event, Trace, Tracer            # noqa: E402

PREFIX = "bloomrf/"


@dataclasses.dataclass
class SpanTrace(Trace):
    """A ``Trace`` that also holds the program's spans."""

    program: list = dataclasses.field(default_factory=list)
    # [Event] host spans named bloomrf/..., sorted by start

    def to_json(self) -> dict:
        d = super().to_json()
        d["program"] = [dataclasses.astuple(e) for e in self.program]
        return d

    @classmethod
    def from_json(cls, d: dict) -> "SpanTrace":
        t = Trace.from_json(d)
        return cls(t.devices, t.spans,
                   [Event(*e) for e in d.get("program", ())])


# -- device busy time inside intervals ----------------------------------------

class Coverage:
    """Seconds of ``[lo, hi]`` that merged (sorted, disjoint) intervals
    cover, for arrays of ``lo`` and ``hi``: the same as
    ``bench.trace.covered``, in O(log n) per interval."""

    def __init__(self, merged: np.ndarray):
        self.a = merged[:, 0]
        self.b = merged[:, 1]
        self.cum = np.concatenate([[0.0], np.cumsum(self.b - self.a)])

    def _upto(self, t: np.ndarray) -> np.ndarray:
        """Covered seconds of ``(-inf, t]``."""
        i = np.searchsorted(self.a, t, side="right")   # intervals begun
        j = np.maximum(i - 1, 0)
        part = np.minimum(t, self.b[j]) - self.a[j]    # the last begun one
        return np.where(i > 0, self.cum[j] + part, 0.0)

    def __call__(self, lo, hi) -> np.ndarray:
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)
        if not len(self.a):
            return np.zeros(np.broadcast(lo, hi).shape)
        return np.maximum(self._upto(hi) - self._upto(lo), 0.0)


def device_coverage(tr: Trace) -> list:
    """One ``Coverage`` per device plane that ran anything."""
    return [Coverage(m) for m in trace.busy_per_device(tr).values()
            if len(m)]


def busy_inside(covs: list, lo, hi) -> np.ndarray:
    """Device busy seconds inside each ``[lo, hi]``, averaged over the
    planes (as ``bench.trace.step_device_busy`` averages them)."""
    if not covs:
        return np.zeros(np.broadcast(np.asarray(lo), np.asarray(hi)).shape)
    return np.mean([c(lo, hi) for c in covs], axis=0)


# -- the reductions the readers share -----------------------------------------

def span_ms_per_step(run, name: str, less_device: bool = False):
    """Summed time of the program's spans named ``bloomrf/<name>`` inside
    the traced window, per step, in ms; with ``less_device``, less the
    device's busy time inside each span.  None when the run was not
    traced or its trace holds no program span; 0 when none has that
    name."""
    tr = run.trace
    spans = getattr(tr, "program", None)      # None: untraced, or no spans
    if not spans or tr.window() is None:
        return None
    lo, hi = tr.window()
    mine = [s for s in spans if s.name == PREFIX + name]
    a = np.clip([s.start for s in mine], lo, hi)
    b = np.clip([s.end for s in mine], lo, hi)
    secs = b - a
    if less_device and len(mine):
        secs = secs - busy_inside(device_coverage(tr), a, b)
    return float(secs.sum()) / len(tr.steps()) * 1e3


def leaves(spans: list) -> list:
    """The spans that hold no other span (one thread: spans nest; a span
    that starts as the last one ends, to a tenth of the trace's 1 ns, is
    not inside it)."""
    order = sorted(spans, key=lambda s: (s.start, -s.dur))
    return [s for s, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt.start >= s.end - 1e-10]


def leaf_share(tr: SpanTrace) -> dict:
    """Per step, the host time (wall less device busy) and the part of
    it that leaf program spans cover, in ms, and that part as a %."""
    covs = device_coverage(tr)
    st = tr.steps()
    host = float(np.sum([s.dur for s in st])
                 - busy_inside(covs, [s.start for s in st],
                               [s.end for s in st]).sum())
    lv = leaves(tr.program)
    a = np.asarray([s.start for s in lv], np.float64)
    b = np.asarray([s.end for s in lv], np.float64)
    leaf = float((b - a).sum() - busy_inside(covs, a, b).sum())
    return {"host_ms_per_step": host / len(st) * 1e3,
            "leaf_host_ms_per_step": leaf / len(st) * 1e3,
            "leaf_share_pct": 100.0 * leaf / host if host > 0 else None}


def _labelled(tr: Trace, lo: float, hi: float) -> tuple:
    """``[lo, hi]`` cut where any span (the benchmark's or the program's)
    opens or closes: (starts, ends, innermost span's name)."""
    # a parent opens before a child that starts with it
    spans = sorted((s for s in tr.spans + getattr(tr, "program", [])
                    if s.start < hi and s.end > lo),
                   key=lambda s: (s.start, -s.dur))
    segs = trace._segments(spans, lo, hi)
    a, b, names = zip(*segs) if segs else ((), (), ())
    return np.asarray(a, np.float64), np.asarray(b, np.float64), names


def idle_gaps(tr: Trace, top: int = 12) -> list:
    """The device's idle time in the window split by the innermost span
    open while it waited, program spans included: ``breakdown``'s
    ``idle_gaps``, with ``Coverage``."""
    win = tr.window()
    if win is None:
        return []
    a, b, names = _labelled(tr, *win)
    covs = device_coverage(tr)
    gap = (sum((b - a) - c(a, b) for c in covs) / len(covs) if covs
           else np.zeros(len(a)))
    idle = {}
    for name, g in zip(names, gap):
        if g > 0:
            idle[name] = idle.get(name, 0.0) + float(g)
    return [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
            [:top]]


def slow_steps(tr: SpanTrace, factor: float = 3.0) -> list:
    """Each step over ``factor`` x the median step: (index, ms, the
    innermost span that held most of it, its ms, device busy % inside
    the step)."""
    st = tr.steps()
    if not st:
        return []
    med = float(np.median([s.dur for s in st]))
    covs = device_coverage(tr)
    out = []
    for i, s in enumerate(st):
        if s.dur <= factor * med:
            continue
        a, b, names = _labelled(tr, s.start, s.end)
        held = {}
        for name, d in zip(names, b - a):
            held[name] = held.get(name, 0.0) + float(d)
        name, secs = max(held.items(), key=lambda kv: kv[1])
        busy = float(busy_inside(covs, s.start, s.end))
        out.append([i, round(s.dur * 1e3, 3), name, round(secs * 1e3, 3),
                    round(100.0 * busy / s.dur, 3)])
    return out


# -- recording ----------------------------------------------------------------

def load(path: str) -> SpanTrace:
    """``bench.trace.load``, and the host spans named ``bloomrf/...``."""
    from jax.profiler import ProfileData

    base = trace.load(path)
    program = [Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
               for plane in ProfileData.from_file(path).planes
               if not plane.name.startswith("/device:")
               for line in plane.lines for ev in line.events
               if ev.name.startswith(PREFIX)]
    program.sort(key=lambda e: e.start)
    return SpanTrace(base.devices, base.spans, program)


class SpanTracer(Tracer):
    """The harness's tracer, with the program's spans switched on while
    the profiler records and kept by ``load``."""

    trace = None            # the SpanTrace that load() read
    load_s = None           # seconds load() took

    @contextlib.contextmanager
    def window(self):
        from repro.obs import trace as obs_trace

        with super().window():
            obs_trace.enable()
            try:
                yield
            finally:
                obs_trace.disable()

    def load(self) -> SpanTrace:
        t0 = time.perf_counter()
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        self.trace = load(max(paths, key=os.path.getmtime))
        shutil.rmtree(self.dir, ignore_errors=True)
        self.load_s = time.perf_counter() - t0
        return self.trace


def metric_entries(root: str = registry.ROOT) -> list:
    """The per-layer metrics that read the program's spans and counters,
    as ``BENCHMARK.json`` entries (``bench/spans.json``)."""
    with open(os.path.join(root, "bench", "spans.json")) as f:
        return json.load(f)["per_layer"]


def profile(cell: str, seed: int, seconds: float,
            root: str = registry.ROOT, **run_kw) -> tuple:
    """One traced run of ``cell`` with the program's spans on.

    Returns the run's result object, a summary (the metrics of
    ``bench/spans.json`` that belong to the cell, every program span's
    time and count, the counters, the leaf share, idle gaps, slow steps,
    and the seconds that loading and reducing the trace took) and what
    the readers read (``trace``, ``counters``, and the ``system``).
    ``run_kw`` goes to ``run_cell`` (the CPU tests)."""
    from bench import run as bench_run

    systems = []
    bench_run.Tracer = SpanTracer       # run_cell builds its tracer by name
    try:
        # run_cell hands ``fault`` the system after warm-up; keeping it
        # changes nothing, and leaves its counters and tracer to read
        result = bench_run.run_cell(cell, seed, seconds, True, root=root,
                                    fault=systems.append, **run_kw)
    finally:
        bench_run.Tracer = Tracer
    system = systems[0]
    tr = system.tracer.trace
    t0 = time.perf_counter()
    run = types.SimpleNamespace(trace=tr, counters=system.counters(),
                                system=system)
    metrics = {}
    for m in metric_entries(root):
        if cell in m["workloads"]:
            v = registry.load_reader(m["name"], root).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    names = sorted({s.name[len(PREFIX):] for s in tr.program})
    summary = {
        "metrics": metrics,
        "span_ms_per_step": {n: span_ms_per_step(run, n) for n in names},
        "span_counts": {n: sum(s.name == PREFIX + n for s in tr.program)
                        for n in names},
        "steps": len(tr.steps()),
        "counters": run.counters,
        **leaf_share(tr),
        "idle_gaps": idle_gaps(tr),
        "slow_steps": slow_steps(tr),
    }
    summary["load_s"] = system.tracer.load_s
    summary["reduce_s"] = time.perf_counter() - t0
    return result, summary, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench.run import NoChip, log

    try:
        result, summary, _ = profile(args.workload, args.seed, args.seconds)
    except (NoChip, FileNotFoundError, registry.RegistryError) as e:
        log(f"no result: {e}")
        return 2
    log(f"slow steps (over 3x the traced median: step, ms, the innermost "
        f"span that held most of it, its ms, device busy %): "
        f"{summary['slow_steps'] or 'none'}")
    print(json.dumps(result), flush=True)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
