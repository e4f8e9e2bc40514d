#!/usr/bin/env python3
"""Drive bloomRF's main path once on one TPU chip and check every answer.

    python chip_smoke.py [--seed N]

Everything goes through the public entry point ``repro.open_filter`` and
every datum comes from ``--seed``.  Two phases:

* **filter** — a 2^25-key u32 filter at 14 bits/key (~59 MB of state in
  HBM: the partitioned kernel tier) takes 2^25 inserts, 2^20 point
  queries (half present) and 2^20 ranges of 2^4..2^10 codes; a 2^20-key
  filter (~1.8 MB: the VMEM-resident tier, insert kernel included) takes
  the same mix at 2^18 queries.
* **store** — an LSM store at default fanout and memtable loads 2^22
  zipf-skewed puts, enough that its run stack exceeds the kernels' VMEM
  budget and stays in HBM, then answers 2^16 short scans through
  ``scan_many`` (at most 2^8 codes wide: tens of rows in the hottest
  cluster) and the same bounds through ``encode_scan_bounds`` +
  ``scan_probe_device``.

Every verdict is checked against a plain reference (a sorted numpy array
and ``searchsorted``, not the repo's engine): zero false negatives, scan
results equal to the reference, and kernel verdicts bit-identical to the
XLA engine on a sample.  The obs dispatch counters must show the kernels
ran, compiled (``interpret=False``).  Times printed here are one smoke
run's wall clock, not a benchmark.

The script exits non-zero, without a result line, unless JAX's first
device is a TPU; it needs the repo's ``src/`` beside it.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.  JAX's persistent
compilation cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` beside this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

BIG_N = 1 << 25
BIG_QUERIES = 1 << 20
SMALL_N = 1 << 20
SMALL_QUERIES = 1 << 18
STORE_PUTS = 1 << 22
STORE_SCANS = 1 << 16
BITS_PER_KEY = 14
XLA_SAMPLE = 1 << 14          # queries re-probed through the XLA engine


def fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    print(json.dumps({"ok": False, "error": msg}))
    return 1


class CompileClock:
    """Seconds JAX spends in backend compiles (a persistent-cache hit
    records only its retrieval time), plus persistent-cache hits."""

    def __init__(self, jax):
        from jax._src import dispatch

        self.seconds = 0.0
        self.hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _range_truth(keys_sorted, lo, hi):
    import numpy as np

    idx = np.searchsorted(keys_sorted, lo)
    at = np.minimum(idx, len(keys_sorted) - 1)
    return (idx < len(keys_sorted)) & (keys_sorted[at] <= hi)


def _point_truth(keys_sorted, q):
    return _range_truth(keys_sorted, q, q)


def filter_phase(n: int, n_queries: int, seed: int, report,
                 backend: str = "auto") -> None:
    """One single-placement u32 filter: insert, point and range probes."""
    import numpy as np

    import repro
    from repro.obs import metrics

    rng = np.random.default_rng([seed, n])
    keys = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    ks = np.unique(keys)
    half = n_queries // 2
    points = np.concatenate([
        rng.choice(keys, half),
        rng.integers(0, 1 << 32, n_queries - half,
                     dtype=np.uint64).astype(np.uint32)])
    width = rng.integers(1 << 4, (1 << 10) + 1, n_queries, dtype=np.uint64)
    lo = np.concatenate([
        # half the ranges straddle a stored key: a miss is a false negative
        rng.choice(keys, half).astype(np.uint64)
        - rng.integers(0, 1 << 4, half, dtype=np.uint64),
        rng.integers(0, 1 << 32, n_queries - half, dtype=np.uint64)])
    lo = np.minimum(lo, (1 << 32) - 1)          # wrapped below zero
    hi = np.minimum(lo + width - 1, (1 << 32) - 1)
    lo, hi = lo.astype(np.uint32), hi.astype(np.uint32)

    f = repro.open_filter(repro.FilterSpec(
        dtype="u32", placement="single", n=n, bits_per_key=BITS_PER_KEY,
        backend=backend))
    if f.ops is None:
        raise AssertionError(f"filter n={n} took the XLA engine, not the "
                             f"kernels (backend={f.backend})")
    if f.ops.interpret:
        raise AssertionError("kernels would run in interpret mode")
    tier = "resident" if f.ops.resident else "partitioned"
    before = metrics.registry().snapshot()

    t0 = time.perf_counter()
    f.insert(keys)
    f.state.block_until_ready()
    t_ins = time.perf_counter() - t0
    t0 = time.perf_counter()
    pv = f.point(points)
    t_pt = time.perf_counter() - t0
    t0 = time.perf_counter()
    rv = f.range(lo, hi)
    t_rg = time.perf_counter() - t0

    after = metrics.registry().snapshot()
    ticks = {k.rsplit("/", 1)[-1]: after.get(k, 0) - before.get(k, 0)
             for k in after if k.startswith("kernel/dispatch/")}
    if ticks.get(tier, 0) <= 0 or any(
            v for k, v in ticks.items() if k != tier and k != "xla"):
        raise AssertionError(f"dispatch counters {ticks} do not show the "
                             f"{tier} tier")
    if f.ops.resident and ticks.get("xla", 0):
        raise AssertionError(f"resident filter fell to XLA: {ticks}")

    pt = _point_truth(ks, points)
    rt = _range_truth(ks, lo, hi)
    fn_p = int((pt & ~pv).sum())
    fn_r = int((rt & ~rv).sum())
    if fn_p or fn_r:
        raise AssertionError(f"false negatives: {fn_p} point, {fn_r} range")
    # the kernels' verdicts are bit-identical to the XLA engine's
    s = slice(0, XLA_SAMPLE)
    xp = np.asarray(f.filter.point(f.state, points[s]))
    xr = np.asarray(f.filter.range(f.state, lo[s], hi[s]))
    if not (np.array_equal(xp, pv[s]) and np.array_equal(xr, rv[s])):
        raise AssertionError("kernel verdicts differ from the XLA engine")
    report(n=n, state_bytes=int(f.state.nbytes), tier=tier,
           dispatch=ticks, point_queries=n_queries, range_queries=n_queries,
           point_fpr=round(float(pv[~pt].mean()), 5),
           range_fpr=round(float(rv[~rt].mean()), 5),
           smoke_wall_s={"insert": round(t_ins, 3), "point": round(t_pt, 3),
                         "range": round(t_rg, 3)})


def _zipf_keys(rng, n):
    """Zipf-clustered u32 keys: hot clusters with repeated (updated) keys."""
    import numpy as np

    z = rng.zipf(1.2, n).astype(np.float64)
    z = z / (z.max() + 1.0)
    jitter = rng.integers(0, 1 << 22, n, dtype=np.uint64)
    return np.minimum((z * float(1 << 31)).astype(np.uint64) + jitter,
                      np.uint64((1 << 32) - 1))


def store_phase(n_puts: int, n_scans: int, seed: int, report,
                backend: str = "auto") -> None:
    """The LSM store: zipf puts, then scans on the host and device paths."""
    import numpy as np

    import repro
    from repro.kernels import read_vmem_budget_u32
    from repro.obs import metrics

    rng = np.random.default_rng([seed, n_puts])
    keys = _zipf_keys(rng, n_puts)
    vals = rng.integers(0, 1 << 31, n_puts, dtype=np.uint64)

    st = repro.open_filter(repro.FilterSpec(
        dtype="u32", placement="store", bits_per_key=BITS_PER_KEY,
        backend=backend))
    t0 = time.perf_counter()
    for k, v in zip(keys.tolist(), vals.tolist()):
        st.put(k, v)
    st.flush()                  # scans then walk runs only
    t_load = time.perf_counter() - t0

    # the reference: last write wins, as a sorted array
    last = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        last[k] = v
    rk = np.fromiter(last.keys(), np.uint64, len(last))
    order = np.argsort(rk)
    rk, rv = rk[order], np.fromiter(last.values(), np.uint64,
                                    len(last))[order]

    half = n_scans // 2
    lo = np.concatenate([
        rng.choice(keys, half) - rng.integers(0, 1 << 8, half,
                                              dtype=np.uint64),
        rng.integers(0, 1 << 32, n_scans - half, dtype=np.uint64)])
    lo = np.minimum(lo, (1 << 32) - 1)
    hi = np.minimum(lo + rng.integers(0, 1 << 8, n_scans, dtype=np.uint64),
                    (1 << 32) - 1)

    inner = st.store
    inner._refresh()
    if inner._scan_kernel_mode() != "kernel":
        raise AssertionError("the store did not select the scan kernel")
    _, stack, _, _, resident = inner._kernel_inputs()
    budget = read_vmem_budget_u32()
    if resident or stack.size <= budget:
        raise AssertionError(f"run stack of {stack.size} lanes fits the "
                             f"{budget}-lane VMEM budget: no HBM tier")

    t0 = time.perf_counter()
    got = st.scan_many(lo, hi)
    t_scan = time.perf_counter() - t0
    a = np.searchsorted(rk, lo)
    b = np.searchsorted(rk, hi, side="right")
    for i in range(n_scans):
        want = list(zip(rk[a[i]:b[i]].tolist(), rv[a[i]:b[i]].tolist()))
        if got[i] != want:
            raise AssertionError(f"scan {i} [{lo[i]}, {hi[i]}]: "
                                 f"{len(got[i])} rows, want {len(want)}")

    before = metrics.registry().snapshot().get("store/scan_probe_batches", 0)
    t0 = time.perf_counter()
    clo, chi = st.encode_scan_bounds(lo, hi)
    fence_d, touch_d = st.scan_probe_device(clo, chi)
    touch = np.asarray(touch_d)
    fence = np.asarray(fence_d)
    t_dev = time.perf_counter() - t0
    batches = (metrics.registry().snapshot().get("store/scan_probe_batches", 0)
               - before)
    if batches <= 0:
        raise AssertionError("store/scan_probe_batches did not tick")
    runs = inner.live_runs()
    for r, run in enumerate(runs):
        rkeys = np.asarray(run.keys, np.uint64)
        want_fence = (hi >= rkeys.min()) & (lo <= rkeys.max())
        if not np.array_equal(fence[:, r], want_fence):
            raise AssertionError(f"run {r}: fence mask differs")
        holds = _range_truth(rkeys, lo, hi)     # tombstones count too
        if (holds & ~touch[:, r]).any():
            raise AssertionError(f"run {r}: a run holding a key in range "
                                 f"was pruned (false negative)")
    report(puts=n_puts, distinct_keys=int(rk.size), runs=len(runs),
           stack_lanes=int(stack.size), stack_bytes=int(stack.nbytes),
           vmem_budget_lanes=budget, tier="hbm",
           scans=n_scans, rows_returned=int((b - a).sum()),
           scan_probe_batches=int(batches),
           runs_touched_per_scan=round(float(touch.sum()) / n_scans, 4),
           smoke_wall_s={"load": round(t_load, 3), "scan_many":
                         round(t_scan, 3), "scan_probe_device":
                         round(t_dev, 3)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        return fail(f"no src/repro beside {__file__}: run it from a "
                    f"checkout of the repository")
    # the entry points run with 64-bit keys enabled, process-wide
    os.environ.setdefault("JAX_ENABLE_X64", "1")
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"no TPU: JAX's first device is {dev.platform!r}")

    from repro.obs import metrics

    metrics.enable()
    clock = CompileClock(jax)
    print(f"chip_smoke: device {dev.device_kind} x{len(devices)}, "
          f"seed {args.seed}")

    def run(name, fn, *a):
        print(f"chip_smoke: phase {name} ...", flush=True)
        t0 = time.perf_counter()
        c0, h0 = clock.seconds, clock.hits
        fields = {}
        try:
            fn(*a, args.seed, fields.update)
        except AssertionError as e:
            print(f"chip_smoke: phase {name} FAILED: {e}", file=sys.stderr)
            return False
        stats = dev.memory_stats() or {}
        fields.update(
            compile_s=round(clock.seconds - c0, 3),
            cache_hits=clock.hits - h0,
            peak_hbm_bytes=stats.get("peak_bytes_in_use"),
            smoke_phase_wall_s=round(time.perf_counter() - t0, 3))
        print(f"chip_smoke: phase {name} ok " + json.dumps(fields))
        return True

    ok = all([run("filter_2e25", filter_phase, BIG_N, BIG_QUERIES),
              run("filter_2e20", filter_phase, SMALL_N, SMALL_QUERIES),
              run("store", store_phase, STORE_PUTS, STORE_SCANS)])
    if not ok:
        return fail("a phase failed")
    print(f"chip_smoke: total compile {clock.seconds:.3f} s, "
          f"cache hits {clock.hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
