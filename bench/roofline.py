"""Bytes a bloomRF probe has to read, from the layout in the config file.

A layout is its domain bits ``d``, its layer distances ``deltas``
(bottom first) and its hash ``replicas`` per layer.  Layer ``i`` keeps,
for each block of keys at level ``l_{i+1} = deltas[0] + ... + deltas[i]``,
one word of ``2^(deltas[i] - 1)`` bits per replica.  A range probe reads
at each layer the words of the blocks that hold its two ends (one word
when both ends share a block); a point probe reads one word per layer.
Words are read as whole 32-bit lanes.  So the count does not depend on
how the program plans or gathers, and the least time it gives is a
lower bound on any implementation's.
"""
from __future__ import annotations

import numpy as np


def _layers(layout: dict):
    level = 0
    for delta, reps in zip(layout["deltas"], layout["replicas"]):
        level += delta
        lanes = max(1, (1 << (delta - 1)) // 32)
        yield level, lanes * reps


def range_probe_bytes(layout: dict, lo: np.ndarray, hi: np.ndarray) -> int:
    lo = np.asarray(lo, np.uint64)
    hi = np.asarray(hi, np.uint64)
    lanes = 0
    for level, per_word in _layers(layout):
        sh = np.uint64(level)
        words = 1 + ((lo >> sh) != (hi >> sh)).astype(np.int64)
        lanes += int(words.sum()) * per_word
    return 4 * lanes


def point_probe_bytes(layout: dict, count: int) -> int:
    return 4 * count * sum(per_word for _, per_word in _layers(layout))


def least_seconds(nbytes: int, peaks: dict) -> float:
    """Bytes over the chip's HBM bandwidth: the memory-bound floor (a
    probe does a few integer operations per word, far under the compute
    peak, so bandwidth is the bound)."""
    return nbytes / float(peaks["hbm_bytes_per_s"])
