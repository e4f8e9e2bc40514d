"""The one traffic generator: reads a mix from ``bench/workloads/<name>.json``.

A run is a closed loop of steps.  One step holds one operation from each
of ``clients`` clients; the driver issues a step's reads as batches and
its writes one by one.  Steps come in blocks of ``steps_per_block``, and
every block has the same multiset of sizes on every seed: the count of
each operation kind, the per-step batch sizes, the scan lengths and
range widths, the split of range anchors.  The seed decides their order
and the keys.  So two seeds do the same amount of work, and the set of
batch shapes (what has to be compiled) is fixed by the mix alone.

Operation kinds and the mix keys they read:

* ``scan``   - ``request_distribution`` picks the start record; the scan
  covers ``scan_length`` loaded records from there (``hi`` is the key
  ``len - 1`` ranks after ``lo`` in the loaded set);
* ``read``   - a point read of a record picked by ``request_distribution``;
* ``update`` - a new value for a record picked the same way;
* ``insert`` - a new key, not loaded or inserted before, with a new value;
* ``range``  - a key range of a width from ``range_width``, anchored at
  ``anchors``: ``stored`` starts up to ``below_stored`` codes below a
  stored key, ``uniform`` starts anywhere in the key domain;
* ``point``  - a point probe at a stored key or a uniform one (``anchors``).

``replay_steps`` (read-only mixes): that many steps are made before the
window and replayed in turn, so the window spends no time making queries.

Request distributions: ``uniform`` over the records, or YCSB's
``scrambled_zipfian`` (Cooper et al., SoCC 2010): a zipfian rank over
``item_count`` items with constant ``theta``, hashed with 64-bit FNV-1a
onto the records.  Its ranks come from stratified quantiles, so a block
picks the same records on every seed.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

READS = ("scan", "read", "range", "point")
WRITES = ("insert", "update")
KINDS = READS + WRITES

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


@dataclasses.dataclass
class Step:
    """One closed-loop step: each client's next operation."""

    index: int
    scan_lo: np.ndarray
    scan_hi: np.ndarray
    scan_len: np.ndarray            # loaded records each scan asks for
    read_keys: np.ndarray
    range_lo: np.ndarray
    range_hi: np.ndarray
    point_keys: np.ndarray
    writes: list                    # [(key, value)] in issue order
    origin: int = -1                # the generated step this one replays

    @property
    def n_ops(self) -> int:
        return (len(self.scan_lo) + len(self.read_keys) + len(self.range_lo)
                + len(self.point_keys) + len(self.writes))


def seed_words(seed: int) -> list:
    """A seed of any size as non-negative 32-bit words for numpy."""
    seed = int(seed) & ((1 << 128) - 1)
    return [(seed >> s) & 0xFFFFFFFF for s in range(0, 128, 32)]


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over the 8 bytes of each value."""
    x = np.asarray(x, np.uint64)
    h = np.full(x.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h = (h ^ ((x >> np.uint64(8 * i)) & np.uint64(0xFF))) * _FNV_PRIME
    s = h.view(np.int64)
    # Java's Math.abs; the one value it cannot negate stays as it is
    return np.where(s < 0, -s, s).view(np.uint64)


def zipfian_ranks(u: np.ndarray, item_count: float, theta: float,
                  zetan: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextValue`` (Gray et al.), vectorised over
    the uniform draws ``u`` in [0, 1)."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / item_count) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ranks = np.floor(item_count * (eta * u - eta + 1.0) ** alpha)
    ranks = np.where(uz < zeta2, 1.0, ranks)
    ranks = np.where(uz < 1.0, 0.0, ranks)
    return ranks.astype(np.uint64)


def spread(count: int, lo: int, hi: int) -> np.ndarray:
    """``count`` integers spread evenly over ``[lo, hi]``: the same
    multiset for every seed (a uniform distribution, stratified)."""
    if count <= 0:
        return np.zeros(0, np.int64)
    return lo + (np.arange(count, dtype=np.int64) * (hi - lo + 1)) // count


def split_counts(total: int, shares: dict) -> dict:
    """Integer counts per kind summing to ``total`` (largest remainder)."""
    raw = {k: total * float(v) for k, v in shares.items()}
    out = {k: int(np.floor(v)) for k, v in raw.items()}
    rest = total - sum(out.values())
    for k in sorted(raw, key=lambda k: (out[k] - raw[k], k))[:rest]:
        out[k] += 1
    return out


class Dataset:
    """What the generator needs to know of the loaded data.

    ``keys[i]`` is record ``i``'s key; ``sorted_keys`` the distinct loaded
    keys ascending and ``rank[i]`` record ``i``'s position there.  Fresh
    keys for inserts are drawn uniformly from the domain and never repeat
    a loaded or earlier inserted key."""

    def __init__(self, keys: np.ndarray, key_bits: int, value_bytes: int = 0):
        self.keys = np.asarray(keys, np.uint64)
        self.key_bits = key_bits
        self.value_bytes = value_bytes
        self._taken = None

    @functools.cached_property
    def _order(self) -> tuple:
        keys, inv = np.unique(self.keys, return_inverse=True)
        return keys, inv.reshape(-1)

    @property
    def sorted_keys(self) -> np.ndarray:
        return self._order[0]

    @property
    def rank(self) -> np.ndarray:
        return self._order[1]

    @property
    def n(self) -> int:
        return len(self.keys)

    def fresh_keys(self, rng, count: int) -> np.ndarray:
        if self._taken is None:
            self._taken = set(self.sorted_keys.tolist())
        out = []
        while len(out) < count:
            for k in rng.integers(0, 1 << self.key_bits, count - len(out),
                                  dtype=np.uint64).tolist():
                if k not in self._taken:
                    self._taken.add(k)
                    out.append(k)
        return np.asarray(out, np.uint64)

    def new_values(self, rng, count: int) -> list:
        b = self.value_bytes
        blob = rng.bytes(count * b)
        return [blob[i * b:(i + 1) * b] for i in range(count)]


class Traffic:
    """Steps of one traffic mix over one dataset, drawn from one seed."""

    def __init__(self, mix: dict, seed: int, data: Dataset):
        unknown = set(mix["mix"]) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown operation kinds {sorted(unknown)}")
        if mix.get("loop", "closed") != "closed":
            raise ValueError("only closed-loop traffic is generated")
        self.spec = mix
        self.seed = seed
        self.data = data
        self.clients = int(mix["clients"])
        self.steps_per_block = int(mix.get("steps_per_block", 1))
        total = self.clients * self.steps_per_block
        self.block_counts = split_counts(total, mix["mix"])
        self._block = []
        self._next = 0
        # a read-only mix may replay a pool of steps made here, in set-up,
        # so that making queries costs the window nothing
        self.replay = int(mix.get("replay_steps", 0))
        self._pool = []
        if self.replay:
            if any(self.block_counts.get(k) for k in WRITES):
                raise ValueError("replay_steps needs a read-only mix: "
                                 "writes must be fresh")
            self._pool = [self._generate() for _ in range(self.replay)]
            self._next = 0

    def batch_shapes(self) -> dict:
        """Every batch size a step of this mix issues, per read kind."""
        per_step = self._per_step_counts(np.random.default_rng(0))
        return {k: sorted(set(int(c) for c in v))
                for k, v in per_step.items() if k in READS and v.sum()}

    def next_step(self) -> Step:
        if not self._pool:
            return self._generate()
        src = self._pool[self._next % self.replay]
        step = dataclasses.replace(src, index=self._next, origin=src.origin)
        self._next += 1
        return step

    def _generate(self) -> Step:
        if not self._block:
            self._block = self._make_block(self._next // self.steps_per_block)
        step = self._block.pop(0)
        step.origin = step.index
        self._next += 1
        return step

    # -- one block ----------------------------------------------------------
    def _per_step_counts(self, rng) -> dict:
        """Each kind's ops per step: the rarer kinds spread evenly, the
        most common one fills each step up to ``clients``."""
        S = self.steps_per_block
        kinds = sorted(self.block_counts, key=lambda k: -self.block_counts[k])
        out = {}
        for k in kinds[1:]:
            c = self.block_counts[k]
            per = np.full(S, c // S, np.int64)
            per[rng.permutation(S)[:c % S]] += 1
            out[k] = per
        rest = self.clients - sum(out.values(), np.zeros(S, np.int64))
        if (rest < 0).any():
            raise ValueError("the mix does not fit its clients per step")
        out[kinds[0]] = rest
        return out

    def _records(self, rng, count: int) -> np.ndarray:
        dist = self.spec.get("request_distribution", {"kind": "uniform"})
        if dist["kind"] == "uniform":
            return rng.integers(0, self.data.n, count, dtype=np.int64)
        if dist["kind"] == "scrambled_zipfian":
            # stratified draws: every seed picks the same multiset of ranks,
            # so the same records, in another order
            u = (np.arange(count) + 0.5) / count
            r = zipfian_ranks(u[rng.permutation(count)],
                              float(dist["item_count"]),
                              float(dist["theta"]), float(dist["zetan"]))
            return (fnv1a64(r) % np.uint64(self.data.n)).astype(np.int64)
        raise ValueError(f"unknown request distribution {dist['kind']!r}")

    def _anchored(self, rng, count: int) -> np.ndarray:
        """Range starts or point keys: at stored keys or uniform."""
        shares = self.spec.get("anchors", {"uniform": 1.0})
        n = split_counts(count, shares)
        dmax = (1 << self.data.key_bits) - 1
        stored = self.data.keys[rng.integers(0, self.data.n,
                                             n.get("stored", 0))]
        below = int(self.spec.get("below_stored", 0))
        if below:
            off = rng.integers(0, below, len(stored), dtype=np.uint64)
            stored = np.where(stored >= off, stored - off, 0)
        uniform = rng.integers(0, dmax + 1, n.get("uniform", 0),
                               dtype=np.uint64)
        out = np.concatenate([stored.astype(np.uint64), uniform])
        return out[rng.permutation(count)]

    def _make_block(self, b: int) -> list:
        rng = np.random.default_rng(seed_words(self.seed) + [b])
        per = self._per_step_counts(rng)
        c = self.block_counts
        data = self.data
        cols = {}
        if c.get("scan"):
            rec = self._records(rng, c["scan"])
            sl = self.spec["scan_length"]
            length = spread(c["scan"], sl["min"], sl["max"])[
                rng.permutation(c["scan"])]
            last = np.minimum(data.rank[rec] + length - 1,
                              len(data.sorted_keys) - 1)
            cols["scan"] = (data.keys[rec], data.sorted_keys[last], length)
        if c.get("read"):
            cols["read"] = (data.keys[self._records(rng, c["read"])],)
        if c.get("range"):
            lo = self._anchored(rng, c["range"])
            rw = self.spec["range_width"]
            width = spread(c["range"], rw["min"], rw["max"])[
                rng.permutation(c["range"])].astype(np.uint64)
            dmax = np.uint64((1 << data.key_bits) - 1)
            hi = np.where(lo > dmax - (width - 1), dmax, lo + width - 1)
            cols["range"] = (lo, hi)
        if c.get("point"):
            cols["point"] = (self._anchored(rng, c["point"]),)
        writes = []
        if c.get("insert"):
            writes.append(("insert", list(zip(
                data.fresh_keys(rng, c["insert"]).tolist(),
                data.new_values(rng, c["insert"])))))
        if c.get("update"):
            keys = data.keys[self._records(rng, c["update"])].tolist()
            writes.append(("update", list(zip(
                keys, data.new_values(rng, c["update"])))))
        empty = np.zeros(0, np.uint64)
        at = {k: 0 for k in KINDS}
        steps = []
        for s in range(self.steps_per_block):
            def take(kind, col=0):
                n = int(per[kind][s]) if kind in per else 0
                if kind not in cols:
                    return empty
                return cols[kind][col][at[kind]:at[kind] + n]

            step_writes = []
            for kind, pairs in writes:
                n = int(per[kind][s])
                step_writes.extend(pairs[at[kind]:at[kind] + n])
            step = Step(
                index=b * self.steps_per_block + s,
                scan_lo=take("scan"), scan_hi=take("scan", 1),
                scan_len=take("scan", 2),
                read_keys=take("read"),
                range_lo=take("range"), range_hi=take("range", 1),
                point_keys=take("point"), writes=step_writes)
            for k in per:
                at[k] += int(per[k][s])
            steps.append(step)
        return steps
