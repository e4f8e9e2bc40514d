"""Arithmetic shared by the metric readers: rates, tails, per-step means."""
from __future__ import annotations

import numpy as np


def ops_per_s(run) -> float:
    """Every operation completed in the window over the window's seconds."""
    return float(run.step_ops.sum()) / run.window_s


def op_percentile_ms(run, q: float) -> float:
    """The ``q``-th percentile over all operations (nearest rank), where
    an operation's latency is its step's wall time."""
    order = np.argsort(run.step_s, kind="stable")
    cum = np.cumsum(run.step_ops[order])
    rank = int(np.ceil(q / 100.0 * cum[-1]))
    i = int(np.searchsorted(cum, max(rank, 1)))
    return float(run.step_s[order][i]) * 1e3


def per_step_ms(run, seconds: float) -> float | None:
    """Seconds of the traced window spread over its steps, in ms."""
    n = len(run.trace.steps()) if run.trace is not None else 0
    return seconds / n * 1e3 if n else None


def host_ms_per_step(run) -> float | None:
    """Mean over the traced steps of wall time less the device's busy
    time inside the step, in ms: what the host spends per step."""
    from bench.trace import step_device_busy

    if run.trace is None:
        return None
    steps = step_device_busy(run.trace)
    if not steps:
        return None
    return sum(wall - busy for wall, busy in steps) / len(steps) * 1e3


def window_report(run, gc_clock) -> list:
    """Lines that say where the window's time went, so that a wide spread
    can be traced to stalls or to a uniform slowdown: the step-time
    distribution, the steps over 3x the median (count, seconds, the
    slowest with their indices), the seconds outside steps, the store's
    background work, and Python's collections by generation."""
    ss = run.step_s
    lines = []
    if len(ss):
        p50, p95, p99, mx = np.percentile(ss * 1e3, [50, 95, 99, 100])
        lines.append(f"step ms p50 {p50:.3f} p95 {p95:.3f} p99 {p99:.3f} "
                     f"max {mx:.3f}")
        slow = np.flatnonzero(ss > 3 * np.median(ss))
        worst = [(int(i), round(float(ss[i]) * 1e3, 1))
                 for i in slow[np.argsort(ss[slow])[::-1][:8]]]
        lines.append(
            f"steps over 3x p50: {len(slow)}, {ss[slow].sum():.3f} s; "
            f"slowest (step, ms) {worst}; "
            f"step seconds by thirds of the steps "
            f"{[round(float(t.sum()), 3) for t in np.array_split(ss, 3)]}")
    lines.append(f"outside steps: {run.window_s - float(ss.sum()):.3f} s of "
                 f"the {run.window_s:.3f} s window")
    bg = {k: run.counters[k] for k in ("flushes", "compactions")
          if k in run.counters}
    if bg:
        lines.append("background work in the window: " + ", ".join(
            f"{k} {v}" for k, v in bg.items()))
    lines.append(f"gc in the window: collections {gc_clock.count} by "
                 f"generation, {[round(x, 3) for x in gc_clock.seconds]} s")
    return lines
