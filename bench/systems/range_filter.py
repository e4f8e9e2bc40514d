"""Driver of a standalone point-range filter (``placement="single"``).

Set-up draws ``keys`` uniform keys of ``key_bits`` bits from the seed,
opens the filter through ``repro.open_filter`` and inserts them all.  A
step sends its ranges as one ``range`` call and its points as one
``point`` call; an answer is in when the verdicts are on the host.

The check compares every verdict of the window with ``reference/
range_filter``'s exact answer: no false negative is allowed (a verdict
a batch did not return reads as "no"), and the share of the window's
truly empty ranges (points) answered "maybe" is held to the
configuration's ``range_fpr`` (``point_fpr``) limit where it names one.
"""
from __future__ import annotations

import time

import numpy as np

from bench import registry
from bench.traffic import Dataset, seed_words


class System:
    def __init__(self, config: dict, seed: int, tracer, log):
        self.cfg = config
        self.seed = seed
        self.tracer = tracer
        self.log = log
        self.ref = registry.load_reference(config["reference"])
        self.windows = []           # per step: (ranges, points, origin)

    def setup(self) -> dict:
        import repro

        cfg = self.cfg
        n = int(cfg["keys"])
        bits = int(cfg["key_bits"])
        rng = np.random.default_rng(seed_words(self.seed) + [0x10AD])
        t0 = time.perf_counter()
        keys = rng.integers(0, 1 << bits, n, dtype=np.uint64)
        self.data = Dataset(keys, bits)
        t_data = time.perf_counter() - t0
        self.filter = repro.open_filter(repro.FilterSpec(**cfg["spec"]))
        t0 = time.perf_counter()
        self.filter.insert(keys)
        self.filter.state.block_until_ready()
        t_load = time.perf_counter() - t0
        self.log(f"inserted {n} keys: state {int(self.filter.state.nbytes)} "
                 f"bytes, {self.filter.describe()}")
        return {"data_s": t_data, "load_s": t_load}

    def dataset(self) -> Dataset:
        return self.data

    def warm_up(self, shapes: dict) -> None:
        keys = self.data.keys
        for b in shapes.get("range", ()):
            lo = keys[:b]
            self.filter.range(lo, lo)
        for b in shapes.get("point", ()):
            self.filter.point(keys[:b])

    def step(self, step) -> None:
        span = self.tracer.span
        ranges = points = None
        if len(step.range_lo):
            with span("range"):
                ranges = (step.range_lo, step.range_hi,
                          self.filter.range(step.range_lo, step.range_hi))
        if len(step.point_keys):
            with span("point"):
                points = (step.point_keys, self.filter.point(step.point_keys))
        self.windows.append((ranges, points, step.origin))

    def counters(self) -> dict:
        return {}

    def issued(self, kind: str) -> list:
        """The window's queries of ``kind`` (``range`` or ``point``), one
        entry per distinct batch: ``(times issued, arrays)``, the arrays
        ``(lo, hi)`` or ``(keys,)``.  A replayed batch is listed once."""
        col = 0 if kind == "range" else 1
        width = 2 if kind == "range" else 1
        seen = {}
        for w in self.windows:
            if w[col] is not None:
                times, arrays = seen.get(w[2], (0, w[col][:width]))
                seen[w[2]] = (times + 1, arrays)
        return list(seen.values())

    def check(self, limits: dict) -> tuple:
        ref = self.ref.SortedKeys(self.data.keys)
        tot = {"range": {}, "point": {}}
        truth = {}                  # a replayed step's answers, found once
        for ranges, points, origin in self.windows:
            for kind, got in (("range", ranges), ("point", points)):
                if got is None:
                    continue
                if (kind, origin) not in truth:
                    truth[kind, origin] = (
                        ref.range_truth(got[0], got[1]) if kind == "range"
                        else ref.point_truth(got[0]))
                r = self.ref.compare(truth[kind, origin], got[-1])
                for k, v in r.items():
                    tot[kind][k] = tot[kind].get(k, 0) + v
        get = lambda key: sum(t.get(key, 0) for t in tot.values())
        checks = [{"name": "false_negatives",
                   "value": get("false_negatives"),
                   "limit": limits["false_negatives"]}]
        observed = {}
        for kind, t in tot.items():
            if not t.get("negatives"):
                continue
            fpr = t["false_positives"] / t["negatives"]
            observed[f"{kind}_negatives"] = t["negatives"]
            if f"{kind}_fpr" in limits:
                checks.append({"name": f"{kind}_fpr", "value": fpr,
                               "limit": limits[f"{kind}_fpr"]})
            else:
                observed[f"{kind}_fpr"] = fpr
        return checks, observed

    def close(self) -> None:
        pass
