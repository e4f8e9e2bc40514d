"""Run one benchmark cell and print its result as the last line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the repository root.  The cell, its configuration, its traffic mix
and its metrics are found by name through ``BENCHMARK.json``
(``bench/registry.py``).  A run:

1. refuses to go on unless JAX's first device is a TPU and there are as
   many as the cell asks for (exit 2, no result line);
2. sets up: makes the data from the seed and loads it through the
   program, then warms up every batch shape the traffic issues.  All of
   it, from the start of ``main`` on, is ``setup_s``;
3. runs closed-loop steps for ``--seconds`` (with ``--trace 1`` under the
   profiler, with the benchmark's own spans around each step);
4. after the window, compares every answer with the plain reference and
   prints each compared number beside its limit;
5. prints one JSON object: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (end-to-end with ``--trace 0``, per-layer with
   ``--trace 1``), ``device``, ``breakdown`` (traced runs) and ``checks``.

JAX's persistent compilation cache is the directory that
``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` in the checkout.
The program's ``src/`` must be beside ``bench/``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
import types

import numpy as np

T_START = time.perf_counter()           # set-up is timed from here

if __package__ in (None, ""):       # run as a script: bench/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import registry, stats                       # noqa: E402
from bench.trace import Tracer, breakdown, busy_seconds  # noqa: E402
from bench.traffic import Traffic                       # noqa: E402


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Programs JAX built (compiled, or loaded from its persistent cache:
    both fire the backend-compile event), their seconds, the cache hits
    among them, and how many were built inside the measured window."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self._jax = jax
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.in_window = False
        self.window_programs = 0
        self.window_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._hit)

    def _duration(self, name, secs, **_):
        if name == self._event:
            self.seconds += secs
            self.compiles += 1
            self.window_programs += self.in_window

    def _hit(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self.window_hits += self.in_window

    def close(self):
        mon = self._jax.monitoring
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._hit)


class GcClock:
    """Python's cyclic collections inside the window: count and seconds,
    by generation."""

    def __init__(self):
        self.in_window = False
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            if self.in_window:
                g = info["generation"]
                self.count[g] += 1
                self.seconds[g] += time.perf_counter() - self._t
            self._t = None

    def close(self):
        gc.callbacks.remove(self._cb)


def init_jax(root: str):
    """Import JAX with the program's settings and the checkout's cache."""
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise FileNotFoundError(f"no src/repro in {root}: the benchmark runs "
                                f"from a checkout of the repository")
    # the program's entry points run with 64-bit keys enabled, process-wide
    os.environ.setdefault("JAX_ENABLE_X64", "1")
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def _merge(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: str = registry.ROOT, require_tpu: bool = True,
             config_overrides: dict | None = None,
             traffic_overrides: dict | None = None, fault=None) -> dict:
    """One run of one cell; returns the result object.

    ``require_tpu=False``, the overrides and ``fault`` (called with the
    system after warm-up, to break the timed path) serve the harness's
    own tests on the CPU."""
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, cell_name)
    config = _merge(registry.load_config(cell["config"], root),
                    config_overrides)
    mix = _merge(registry.load_traffic(cell["traffic"], root),
                 traffic_overrides)
    metrics = registry.metrics_for(bench, cell_name, per_layer=trace)
    readers = {m["name"]: registry.load_reader(m["name"], root)
               for m in metrics}

    jax = init_jax(root)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"cell {cell_name} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devices)} {dev.platform} device(s)")
    peaks = registry.load_peaks(dev.device_kind, root) if require_tpu \
        else None
    log(f"cell {cell_name}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {seed}, {seconds} s, trace {int(trace)}; "
        f"device {dev.device_kind} x{len(devices)}")

    clock = CompileClock()
    gclock = GcClock()
    workdir = tempfile.mkdtemp(prefix="bench_run_")
    tracer = Tracer(trace, workdir)
    system = registry.load_system(config["system"], root).System(
        config, seed, tracer, log)
    try:
        t_init = time.perf_counter() - T_START
        split = system.setup()
        traffic = Traffic(mix, seed, system.dataset())
        shapes = traffic.batch_shapes()
        t0 = time.perf_counter()
        system.warm_up(shapes)
        split["warm_up_s"] = time.perf_counter() - t0
        split["init_s"] = t_init
        split["compile_s"] = clock.seconds
        split["compiles"] = clock.compiles
        split["cache_hits"] = clock.hits
        if fault is not None:
            fault(system)
        setup_s = time.perf_counter() - T_START
        log(f"setup_s {setup_s:.3f}: " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in sorted(split.items())) + f"; batch shapes {shapes}")

        step_s, step_ops, step_builds, failed = [], [], [], 0
        clock.in_window = gclock.in_window = True
        with tracer.window():
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                step = traffic.next_step()
                a = time.perf_counter()
                try:
                    with tracer.span("step"):
                        system.step(step)
                except Exception:
                    log("a step failed:\n" + traceback.format_exc())
                    failed += step.n_ops
                    break
                b = time.perf_counter()
                step_s.append(b - a)
                step_ops.append(step.n_ops)
                step_builds.append(clock.window_programs)
                if b >= deadline:
                    break
            window_s = time.perf_counter() - t0
        clock.in_window = gclock.in_window = False
        mem = dev.memory_stats() or {}
        counters = system.counters()
        tr = tracer.load() if trace else None

        checks, observed = system.check(config["limits"])
    finally:
        system.close()
        clock.close()
        gclock.close()
        shutil.rmtree(workdir, ignore_errors=True)

    run = types.SimpleNamespace(
        cell=cell, config=config, traffic=mix, seed=seed,
        step_s=np.asarray(step_s), step_ops=np.asarray(step_ops),
        window_s=window_s, setup_s=setup_s, setup=split, counters=counters,
        compiles_in_window=clock.window_programs,
        step_builds=np.diff(step_builds, prepend=0), trace=tr, peaks=peaks,
        system=system)
    out_metrics = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    for line in stats.window_report(run, gclock):
        log(line)
    log(f"window {window_s:.3f} s, {len(step_s)} steps, "
        f"{int(sum(step_ops))} ops, {clock.window_programs} programs built "
        f"in the window ({clock.window_hits} from the cache); counters "
        f"{json.dumps(counters)}; observed "
        f"{json.dumps(observed)}")
    result = {"correct": failed == 0 and all(c["value"] <= c["limit"]
                                             for c in checks),
              "attempted": int(sum(step_ops)) + failed, "failed": failed,
              "metrics": out_metrics, "device": device}
    if tr is not None:
        busy, win = busy_seconds(tr)
        device["busy_s"], device["window_s"] = busy, win
        result["breakdown"] = breakdown(tr)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        log(f"check {c['name']} = {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (NoChip, FileNotFoundError, registry.RegistryError) as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
