"""Plain reference of a point-range filter: the exact answer.

A range ``[lo, hi]`` holds a key when the first stored key at or above
``lo`` is at most ``hi`` (a sorted array and ``searchsorted``).  A
filter may answer "maybe" where the truth is "no" (a false positive),
never "no" where it is "yes" (a false negative).
"""
from __future__ import annotations

import numpy as np


class SortedKeys:
    def __init__(self, keys: np.ndarray):
        self.keys = np.unique(np.asarray(keys, np.uint64))

    def range_truth(self, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
        lo = np.asarray(lo, np.uint64)
        hi = np.asarray(hi, np.uint64)
        idx = np.searchsorted(self.keys, lo)
        at = np.minimum(idx, len(self.keys) - 1)
        return (idx < len(self.keys)) & (self.keys[at] <= hi)

    def point_truth(self, q: np.ndarray) -> np.ndarray:
        return self.range_truth(q, q)


def compare(truth: np.ndarray, verdicts) -> dict:
    """False negatives and the false-positive count of one batch of
    filter verdicts against the exact answers.  A verdict the batch did
    not return reads as "no"."""
    truth = np.asarray(truth, bool)
    got = np.zeros(len(truth), bool)
    v = np.asarray(verdicts, bool).reshape(-1)[:len(truth)]
    got[:len(v)] = v
    neg = ~truth
    return {"false_negatives": int((truth & ~got).sum()),
            "negatives": int(neg.sum()),
            "false_positives": int((got & neg).sum())}
