"""Shared benchmark utilities: datasets, workloads, timing, FPR measurement,
and the machine-readable JSON emitters the CI gates consume.

Benchmarks mirror the paper's standalone methodology (§9): build a filter
over n keys, issue Q range- (or point-) queries of a fixed size per setting,
and report FPR over empty queries + mean probe latency.  Distributions:
uniform / normal / zipfian for both data and queries (Fig. 9/11).

``timeit`` and ``write_json`` are the single copies of the warm-up-once
timing loop and the ``{schema, rows: [{name, <value>, <detail>}]}`` JSON
shape previously duplicated across the bench drivers — every driver
(``run.py``, ``dist_bench.py``, ``store_bench.py``) routes through them so
the CI validators keep one contract.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
os.environ.setdefault("JAX_ENABLE_X64", "1")
# JAX's persistent compile cache: the caller's directory when set, else a
# fixed one in the checkout (the path is part of every entry's key)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".jax_cache"))

U64MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


def timeit(fn, *args, repeat: int = 3) -> float:
    """Seconds per call: warm up exactly once (compile + drain), then the
    mean of ``repeat`` timed calls (block_until_ready handles pytrees)."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeat


def write_json(path: str, schema: str, rows, value_key: str = "us_per_call",
               detail_key: str = "derived", **extra) -> None:
    """Write ``(name, value, detail)`` rows as the CI benchmark JSON shape."""
    payload = {
        "schema": schema,
        **extra,
        "rows": [{"name": n, value_key: float(u), detail_key: str(d)}
                 for n, u, d in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)


def append_trajectory(path: str, rows, smoke: bool) -> None:
    """Append one timestamped metrics row to the bench-trend JSONL.

    Every ``run.py --json`` invocation adds ``{ts, schema, smoke,
    metrics: {row name: value}}``; ``check_gates.py trajectory`` diffs the
    last N rows and fails on monotone regression — the slow-creep drift a
    single committed baseline can never catch."""
    row = {
        "schema": "bloomrf-trajectory/v1",
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "smoke": bool(smoke),
        "metrics": {n: float(u) for n, u, _ in rows},
    }
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")


def gen_keys(n: int, dist: str, rng: np.random.Generator) -> np.ndarray:
    if dist == "uniform":
        return rng.integers(0, 1 << 63, n, dtype=np.uint64)
    if dist == "normal":
        x = rng.normal(0.5, 0.1, n)
        return (np.clip(x, 0, 1) * float(1 << 62)).astype(np.uint64)
    if dist == "zipf":
        z = rng.zipf(1.2, n).astype(np.float64)
        z = z / (z.max() + 1.0)
        jitter = rng.integers(0, 1 << 32, n, dtype=np.uint64)
        return (z * float(1 << 62)).astype(np.uint64) + jitter
    raise ValueError(dist)


def gen_empty_ranges(keys: np.ndarray, q: int, rsize: int, dist: str,
                     rng: np.random.Generator):
    """Query ranges (mostly empty — the paper's worst case) + truth mask."""
    lo = gen_keys(q, dist, rng)
    hi = lo + np.uint64(max(rsize - 1, 0))
    hi = np.maximum(hi, lo)  # wrap guard
    ks = np.sort(keys)
    idx = np.searchsorted(ks, lo)
    truth = (idx < len(ks)) & (ks[np.minimum(idx, len(ks) - 1)] <= hi)
    return lo, hi, truth


def measure_range(f, keys, lo, hi, truth):
    t0 = time.perf_counter()
    res = f.range(lo, hi)
    dt = time.perf_counter() - t0
    fn = int((truth & ~res).sum())
    assert fn == 0, f"{type(f).__name__}: {fn} range false negatives"
    empties = max(int((~truth).sum()), 1)
    fpr = float((res & ~truth).sum()) / empties
    return fpr, dt / len(lo) * 1e6  # us/query


def measure_point(f, keys, qs, truth):
    t0 = time.perf_counter()
    res = f.point(qs)
    dt = time.perf_counter() - t0
    assert not (truth & ~res).any()
    empties = max(int((~truth).sum()), 1)
    fpr = float((res & ~truth).sum()) / empties
    return fpr, dt / len(qs) * 1e6


def emit(name: str, us_per_call, derived):
    print(f"{name},{us_per_call:.3f},{derived}")
    return (name, us_per_call, derived)
