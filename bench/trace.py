"""The traced window: profiler on, host spans, and the trace's reduction.

``Tracer`` wraps the measured window in a JAX profiler session (no Python
tracer) and the benchmark's own ``TraceAnnotation`` spans.  ``load``
turns the ``.xplane.pb`` it writes into a ``Trace``: device operations,
host spans, all on the trace's one clock, in seconds.  The reductions
below are what every metric reader uses, so each PR computes busy time,
idle gaps and kernel time the same way.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil

import numpy as np

SPAN_PREFIX = "bench/"
STEP_SPAN = "bench/step"

# The program's device programs, by the names it gives them (an op's
# program is the jitted function's name, "jit_<name>"): the one place the
# readers take them from.
PROGRAM = {
    # a standalone filter's range and point probes on the XLA engine
    # (64-bit keys)
    "filter_probe": r" jit_(range|point)$",
    # an LSM store's scan pruning: the XLA stacked probe over every run
    "store_scan": r" jit__range_all$",
}


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds on the trace's clock
    dur: float              # seconds
    module: str = ""        # the program (HLO module) a device op ran in

    @property
    def end(self) -> float:
        return self.start + self.dur

    def matches(self, pattern: str) -> bool:
        return re.search(pattern, f"{self.name} {self.module}") is not None


@dataclasses.dataclass
class Trace:
    """Device ops per device plane, and the benchmark's host spans."""

    devices: dict           # plane name -> [Event] (device ops, sorted)
    spans: list             # [Event] host spans named bench/..., sorted

    def to_json(self) -> dict:
        return {"devices": {k: [dataclasses.astuple(e) for e in v]
                            for k, v in self.devices.items()},
                "spans": [dataclasses.astuple(e) for e in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [Event(*e) for e in v]
                    for k, v in d["devices"].items()},
                   [Event(*e) for e in d["spans"]])

    # -- the window -----------------------------------------------------
    def steps(self) -> list:
        return [s for s in self.spans if s.name == STEP_SPAN]

    def window(self) -> tuple:
        """(start, end) from the first step's start to the last's end."""
        st = self.steps()
        if not st:
            return None
        return st[0].start, max(s.end for s in st)

    def ops(self, lo: float = -np.inf, hi: float = np.inf) -> list:
        """Device ops of every plane that overlap ``[lo, hi]``."""
        return [e for evs in self.devices.values() for e in evs
                if e.end > lo and e.start < hi]


def union(intervals) -> np.ndarray:
    """Merge ``[(start, end)]`` into disjoint sorted intervals, (n, 2)."""
    iv = sorted((float(a), float(b)) for a, b in intervals if b > a)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, np.float64).reshape(-1, 2)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the merged intervals cover."""
    if not len(merged):
        return 0.0
    a = np.maximum(merged[:, 0], lo)
    b = np.minimum(merged[:, 1], hi)
    return float(np.clip(b - a, 0, None).sum())


def busy_per_device(trace: Trace) -> dict:
    """Plane -> merged busy intervals of its device ops."""
    return {p: union((e.start, e.end) for e in evs)
            for p, evs in trace.devices.items()}


def busy_seconds(trace: Trace) -> tuple:
    """(busy, window): device busy seconds inside the window, averaged
    over the device planes that ran anything, and the window's length."""
    win = trace.window()
    if win is None:
        return None, None
    busy = [covered(m, *win) for m in busy_per_device(trace).values()
            if len(m)]
    if not busy:                    # no device ran anything: no reading
        return None, win[1] - win[0]
    return float(np.mean(busy)), win[1] - win[0]


def step_device_busy(trace: Trace) -> list:
    """Per step span: (wall seconds, device busy seconds inside it)."""
    merged = busy_per_device(trace).values()
    out = []
    for s in trace.steps():
        busy = [covered(m, s.start, s.end) for m in merged if len(m)]
        out.append((s.dur, float(np.mean(busy)) if busy else 0.0))
    return out


def idle_pct(trace: Trace) -> float | None:
    """Share of the window in which no operation ran on the device, in %
    (1 - busy union / window); None when no device ran anything."""
    busy, window = busy_seconds(trace)
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)


def op_seconds(trace: Trace, pattern: str) -> float:
    """Summed device time, inside the window, of ops matching
    ``pattern``, averaged over device planes.  A pattern is searched in
    ``"<op> <program>"``: ``PROGRAM`` patterns name whole programs."""
    win = trace.window()
    if win is None:
        return 0.0
    per = []
    for evs in trace.devices.values():
        tot = 0.0
        for e in evs:
            if e.end <= win[0] or e.start >= win[1]:
                continue
            if e.matches(pattern):
                tot += min(e.end, win[1]) - max(e.start, win[0])
        per.append(tot)
    return float(np.mean(per)) if per else 0.0


def _segments(spans: list, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut at span edges, each piece labelled with the
    innermost span open over it (the benchmark's spans nest: one thread)."""
    edges = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                   + [(s.end, 0, i) for i, s in enumerate(spans)])
    out, stack, t = [], [], lo
    for when, opening, i in edges + [(hi, 0, -1)]:
        when = min(max(when, lo), hi)
        if when > t:
            out.append((t, when, spans[stack[-1]].name if stack
                        else "outside bench spans"))
            t = when
        if i < 0:
            break
        if opening:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the device's idle time
    split by the innermost host span open while it waited."""
    win = trace.window()
    if win is None:
        return {"device_ops": [], "idle_gaps": []}
    busy = [m for m in busy_per_device(trace).values() if len(m)]
    n_planes = max(len(busy), 1)
    by_op = {}
    for e in trace.ops(*win):
        op = e.name.split(" = ")[0]         # "%fusion.3 = u32[..] ..."
        key = f"{e.module}:{op}" if e.module else op
        by_op[key] = by_op.get(key, 0.0) + e.dur / n_planes
    idle = {}
    for a, b, label in _segments(trace.spans, *win):
        gap = sum((b - a) - covered(m, a, b) for m in busy) / n_planes
        if gap > 0:
            idle[label] = idle.get(label, 0.0) + gap
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(idle)}


# -- recording ----------------------------------------------------------------

class Tracer:
    """Profiler session and host spans around the measured window."""

    def __init__(self, enabled: bool, workdir: str):
        self.enabled = enabled
        self.dir = os.path.join(workdir, "trace")
        if enabled:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        return self._annotation(SPAN_PREFIX + name)

    @contextlib.contextmanager
    def window(self):
        if not self.enabled:
            yield
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def load(self) -> Trace:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        trace = load(max(paths, key=os.path.getmtime))
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def _module_name(ev_name: str) -> str:
    return ev_name.split("(")[0]            # "jit_f(123...)" -> "jit_f"


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: each device plane's ``XLA Ops`` (each op
    tagged with the ``XLA Modules`` program it ran in) and the host
    spans named ``bench/...``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = [(m.start_ns * 1e-9, (m.start_ns + m.duration_ns) * 1e-9,
                     _module_name(m.name))
                    for m in lines.get("XLA Modules", ())]
            mods.sort()
            ops = sorted((Event(ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9)
                          for ev in lines.get("XLA Ops", ())),
                         key=lambda e: e.start)
            j = 0
            for e in ops:                   # the program each op ran in
                while j < len(mods) and mods[j][1] < e.start:
                    j += 1
                if j < len(mods) and mods[j][0] <= e.start:
                    e.module = mods[j][2]
            if ops:
                devices[plane.name] = ops
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Event(ev.name, ev.start_ns * 1e-9,
                                       ev.duration_ns * 1e-9))
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans)
