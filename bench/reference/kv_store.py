"""Plain reference of the key-value store: a sorted array, last write wins.

It knows nothing of the store under test.  The loaded records are one
sorted key array with their values; writes acknowledged later sit in an
overlay dict.  ``scan(lo, hi)`` is every live ``(key, value)`` with
``lo <= key <= hi`` in key order, ``get(key)`` the value or ``None``.

``read_log`` reads the write-ahead log as its documented frame format
(little-endian ``u32 length | u32 crc32 | pickled (op, key, value)``),
so a check can see that each acknowledged write reached the log.
"""
from __future__ import annotations

import os
import pickle
import struct
import zlib

import numpy as np

_HEADER = struct.Struct("<II")


class SortedKV:
    def __init__(self, keys: np.ndarray, values: list):
        keys = np.asarray(keys, np.uint64)
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.values = [values[i] for i in order.tolist()]
        if len(self.keys) > 1 and (self.keys[1:] == self.keys[:-1]).any():
            raise ValueError("loaded keys must be distinct")
        self.overlay = {}           # key -> value, writes after the load
        self._ov_keys = np.zeros(0, np.uint64)
        self._ov_dirty = False

    def put(self, key: int, value) -> None:
        self.overlay[int(key)] = value
        self._ov_dirty = True

    def get(self, key: int):
        key = int(key)
        if key in self.overlay:
            return self.overlay[key]
        i = int(np.searchsorted(self.keys, np.uint64(key)))
        if i < len(self.keys) and int(self.keys[i]) == key:
            return self.values[i]
        return None

    def scan(self, lo: int, hi: int) -> list:
        if self._ov_dirty:
            self._ov_keys = np.asarray(sorted(self.overlay), np.uint64)
            self._ov_dirty = False
        a = int(np.searchsorted(self.keys, np.uint64(lo), side="left"))
        b = int(np.searchsorted(self.keys, np.uint64(hi), side="right"))
        rows = dict(zip(self.keys[a:b].tolist(), self.values[a:b]))
        oa = np.searchsorted(self._ov_keys, np.uint64(lo), side="left")
        ob = np.searchsorted(self._ov_keys, np.uint64(hi), side="right")
        for k in self._ov_keys[oa:ob].tolist():
            rows[k] = self.overlay[k]
        return sorted(rows.items())


def read_log(directory: str, offsets: dict) -> list:
    """``(op, key, value)`` records framed in ``directory``'s log files
    after the byte offsets in ``offsets`` (file name -> offset); a torn
    or corrupt frame ends a file's records."""
    out = []
    for name in sorted(os.listdir(directory)):
        path = os.path.join(directory, name)
        if not os.path.isfile(path):
            continue
        with open(path, "rb") as f:
            f.seek(offsets.get(name, 0))
            while True:
                head = f.read(_HEADER.size)
                if len(head) < _HEADER.size:
                    break
                length, crc = _HEADER.unpack(head)
                payload = f.read(length)
                if len(payload) < length or zlib.crc32(payload) != crc:
                    break
                out.append(pickle.loads(payload))
    return out


def log_offsets(directory: str) -> dict:
    """Current size of each file in ``directory``."""
    return {n: os.path.getsize(os.path.join(directory, n))
            for n in os.listdir(directory)
            if os.path.isfile(os.path.join(directory, n))}
