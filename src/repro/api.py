"""The typed filter façade: one ``FilterSpec -> open_filter()`` front door
(DESIGN.md §11).

The paper sells bloomRF as a *unified* point-range filter that "supports
floating-points, and can serve as a multi-attribute filter" (§8).  This
module is that claim as an API: a declarative :class:`FilterSpec` names the
key dtype, sizing, tuning budget, placement, and probe backend, and
:func:`open_filter` returns a handle that

* routes every insert/point/range (and store put/get/scan) through the
  order-preserving codecs in ``core/codecs.py`` — typed keys (floats,
  strings, attribute pairs) never leak past the façade into the integer
  filter machinery;
* chooses the filter layout through ``core/tuning.py`` (the paper's §7
  advisor) or the tuning-free ``basic_layout``, instead of requiring a
  hand-built :class:`~repro.core.FilterLayout`;
* dispatches probes to the existing engine / kernels / StackedProbe
  machinery, preserving the one-fused-gather jaxpr invariant behind the
  new surface (asserted in ``tests/test_facade.py``).

Placements map onto the subsystems grown in PRs 1–4:

====================  ====================================================
``single``            one :class:`~repro.core.BloomRF` (XLA engine) or
                      :class:`~repro.kernels.FilterOps` (Pallas kernels)
``bank``              :class:`~repro.dist.filter_bank.FilterBank` —
                      range-partitioned shard rows, stacked one-gather
``tenant``            :class:`~repro.dist.tenant_bank.TenantFilterBank`
                      (+ Bloofi-style meta rows in the same gather)
``store``             the LSM :class:`~repro.store.Store` with per-run
                      filter blocks, wrapped for typed put/get/scan
====================  ====================================================

The pre-façade constructors survive as deprecated shims (``repro._compat``)
pointing at their ``FilterSpec`` equivalents.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .core import codecs as _cd
from .core.layout import basic_layout, require_x64
from .obs import metrics as _obs_metrics
from .obs import trace as _obs_trace

__all__ = ["FilterSpec", "open_filter", "chunked_probe",
           "SingleFilter", "BankFilter", "TenantFilter", "TypedStore"]

_DTYPES = ("u8", "u16", "u32", "u64", "f32", "f64", "str", "multiattr")
_PLACEMENTS = ("single", "bank", "tenant", "store")
_BACKENDS = ("auto", "xla", "resident", "partitioned", "stacked")
_TUNINGS = ("auto", "basic", "advised", "adaptive")
_MUTABILITIES = ("insert_only", "deletable", "ttl")

#: range budget (log2) up to which the tuning-free basic layout is advised
_BASIC_RANGE_LOG2 = 14


# ---------------------------------------------------------------------------
# key codecs: typed keys <-> the integer filter domain
# ---------------------------------------------------------------------------

class _Codec:
    """Order-preserving map from a typed key space into a d-bit uint domain.

    ``encode_point``/``encode_bounds`` are FN-free by construction: every
    key in a typed interval has its code inside the encoded code interval.
    ``codes_per_key`` > 1 means one inserted key sets several codes (the
    multi-attribute dual concatenation).
    """

    name: str
    d: int
    codes_per_key = 1
    exact = True            # encode is injective (store data keys allowed)

    def encode_insert(self, keys) -> np.ndarray:
        return self.encode_point(keys)

    def encode_point(self, qs) -> np.ndarray:
        raise NotImplementedError

    def encode_bounds(self, lo, hi) -> tuple:
        return self.encode_point(lo), self.encode_point(hi)

    def decode(self, code):
        raise NotImplementedError(f"{self.name} codes do not decode")


class _UIntCodec(_Codec):
    def __init__(self, bits: int):
        self.name = f"u{bits}"
        self.d = bits

    def encode_point(self, qs) -> np.ndarray:
        arr = np.atleast_1d(np.asarray(qs, np.uint64))
        if self.d < 64 and (arr >> np.uint64(self.d)).any():
            raise ValueError(
                f"{self.name} keys must fit the {self.d}-bit domain")
        return arr

    def decode(self, code):
        return np.asarray(code, np.uint64)


class _Float64Codec(_Codec):
    name = "f64"
    d = 64

    def encode_point(self, qs) -> np.ndarray:
        arr = np.atleast_1d(np.asarray(qs, np.float64))
        if np.isnan(arr).any():
            raise ValueError("f64 keys must not be NaN (the order-preserving "
                             "φ map has no total order for NaN)")
        return _cd.float64_to_u64(arr)

    def decode(self, code):
        return _cd.u64_to_float64(np.asarray(code, np.uint64))


class _Float32Codec(_Codec):
    name = "f32"
    d = 32

    def encode_point(self, qs) -> np.ndarray:
        arr = np.atleast_1d(np.asarray(qs, np.float32))
        if np.isnan(arr).any():
            raise ValueError("f32 keys must not be NaN (the order-preserving "
                             "φ map has no total order for NaN)")
        return _cd.float32_to_u32(arr).astype(np.uint64)

    def decode(self, code):
        return _cd.u32_to_float32(np.asarray(code, np.uint32))


class _StrCodec(_Codec):
    """SuRF-Hash-style string codes (7-byte prefix + tail hash, paper §8).

    Codes are *not* injective — two strings may share a code — so string
    stores keep per-code buckets (:class:`TypedStore`) and range probes are
    FN-free supersets over the 7-byte prefix order."""

    name = "str"
    d = 64
    exact = False

    def encode_point(self, qs) -> np.ndarray:
        if isinstance(qs, (str, bytes)):
            qs = [qs]
        return np.asarray([_cd.string_point_code(s) for s in qs], np.uint64)

    def encode_bounds(self, lo, hi) -> tuple:
        if isinstance(lo, (str, bytes)):
            lo, hi = [lo], [hi]
        pairs = [_cd.string_range_bounds(a, b) for a, b in zip(lo, hi)]
        return (np.asarray([p[0] for p in pairs], np.uint64),
                np.asarray([p[1] for p in pairs], np.uint64))


class _MultiAttrCodec(_Codec):
    """Two reduced-precision 32-bit attributes, concatenated in both orders
    (paper §8).  Keys and query bounds are ``(a, b)`` pairs; inserts set
    both the <A,B> and <B,A> codes so conjunctive predicates on either
    attribute map to one range probe."""

    name = "multiattr"
    d = 64
    codes_per_key = 2

    @staticmethod
    def _pair(key) -> tuple:
        """Normalise multiattr keys to (a, b) uint64 vectors.

        Accepts a scalar pair ``(a, b)``, the column form
        ``(a_vector, b_vector)``, or a sequence of ``(a, b)`` rows.  The
        ambiguous 2x2 case reads as the column form — pass columns when
        batching."""
        arr = np.asarray(key, np.uint64)
        if arr.ndim == 1 and arr.shape[0] == 2:        # one (a, b) pair
            a, b = arr[0:1], arr[1:2]
        elif arr.ndim == 2 and arr.shape[0] == 2:      # (a_vec, b_vec)
            a, b = arr[0], arr[1]
        elif arr.ndim == 2 and arr.shape[1] == 2:      # rows of (a, b)
            a, b = arr[:, 0], arr[:, 1]
        else:
            raise ValueError(
                f"multiattr keys are (a, b) pairs, column vectors, or "
                f"(N, 2) rows; got array of shape {arr.shape}")
        if (a >> np.uint64(32)).any() or (b >> np.uint64(32)).any():
            raise ValueError("multiattr attributes must fit 32 bits")
        return a, b

    def encode_insert(self, keys) -> np.ndarray:
        ab, ba = _cd.multiattr_insert_codes(*self._pair(keys))
        return np.concatenate([ab, ba])

    def encode_point(self, qs) -> np.ndarray:
        return _cd.pack2x32(*self._pair(qs))

    def encode_bounds(self, lo, hi) -> tuple:
        return self.encode_point(lo), self.encode_point(hi)

    def mirrored_bounds(self, b_const, a_lo, a_hi) -> tuple:
        """<B,A> code bounds for ``B == b_const AND A in [a_lo, a_hi]``."""
        return _cd.multiattr_range_for_a_eq_b_range(
            np.atleast_1d(np.asarray(b_const, np.uint64)),
            np.asarray(a_lo, np.uint64), np.asarray(a_hi, np.uint64))

    def decode(self, code):
        return _cd.unpack2x32(np.asarray(code, np.uint64))


def _codec_for(dtype: str) -> _Codec:
    if dtype in ("u8", "u16", "u32", "u64"):
        return _UIntCodec(int(dtype[1:]))
    return {"f32": _Float32Codec, "f64": _Float64Codec,
            "str": _StrCodec, "multiattr": _MultiAttrCodec}[dtype]()


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FilterSpec:
    """Declarative description of a point-range filter deployment.

    Exactly one of ``bits_per_key`` / ``target_fpr`` sizes the filter
    (neither -> 16 bits/key).  ``range_log2`` is the log2 of the largest
    range the filter is tuned for (the paper's R); ``tuning='auto'`` picks
    the tuning-free basic layout up to R = 2^14 and the §7 advisor above.
    """

    dtype: str = "u64"                      # u8|u16|u32|u64|f32|f64|str|multiattr
    n: int = 1 << 16                        # expected keys (per tenant if tenant)
    bits_per_key: Optional[float] = None
    target_fpr: Optional[float] = None      # range-FPR target at R=2^range_log2
    range_log2: int = _BASIC_RANGE_LOG2     # log2 of the range budget R
    placement: str = "single"               # single|bank|tenant|store
    backend: str = "auto"                   # auto|xla|resident|partitioned|stacked
    tuning: str = "auto"                    # auto|basic|advised
    shards: int = 1                         # bank/tenant: power-of-two shard rows
    tenants: int = 1                        # tenant: tenant rows
    delta: Optional[int] = None             # layer distance override (1..7)
    point_weight: float = 1.0               # advisor's point-vs-range weight
    chunk: int = 1 << 18                    # host-side probe chunking
    seed: int = 0x0B100F11
    # churn model (core/dynamic.py): how inserted keys may leave again
    mutability: str = "insert_only"         # insert_only|deletable|ttl
    generations: int = 4                    # ttl: retained TTL windows (>= 2)
    # store placement knobs (StoreConfig)
    store_backend: str = "bloomrf"
    memtable_limit: int = 4096
    fanout: int = 4
    level0_runs: int = 4
    purge_dead_frac: float = 0.25           # deletable store: dead fraction
                                            # forcing a purge rebuild
    durability: str = "none"                # store: "none" | "wal" (crash-safe
                                            # WAL + checkpoint/recovery)
    wal_dir: Optional[str] = None           # store durable root (WAL +
                                            # snapshots + manifest)

    def __post_init__(self):
        def bad(msg):
            raise ValueError(f"FilterSpec: {msg}")

        if self.dtype not in _DTYPES:
            bad(f"dtype must be one of {_DTYPES}, got {self.dtype!r}")
        if self.placement not in _PLACEMENTS:
            bad(f"placement must be one of {_PLACEMENTS}, "
                f"got {self.placement!r}")
        if self.backend not in _BACKENDS:
            bad(f"backend must be one of {_BACKENDS}, got {self.backend!r}")
        if self.tuning not in _TUNINGS:
            bad(f"tuning must be one of {_TUNINGS}, got {self.tuning!r}")
        if self.n < 1:
            bad(f"n must be >= 1, got {self.n}")
        if self.bits_per_key is not None and self.target_fpr is not None:
            bad("give bits_per_key OR target_fpr, not both")
        if self.bits_per_key is not None and self.bits_per_key <= 0:
            bad(f"bits_per_key must be > 0, got {self.bits_per_key}")
        if self.target_fpr is not None and not (0 < self.target_fpr < 1):
            bad(f"target_fpr must be in (0, 1), got {self.target_fpr}")
        d = _codec_for(self.dtype).d
        if not (0 <= self.range_log2 <= d):
            bad(f"range_log2 must be in [0, {d}] for {self.dtype} keys, "
                f"got {self.range_log2}")
        if self.delta is not None and not (1 <= self.delta <= 7):
            bad(f"delta must be in 1..7, got {self.delta}")
        if self.shards < 1 or self.shards & (self.shards - 1):
            bad(f"shards must be a power of two, got {self.shards}")
        if self.tenants < 1:
            bad(f"tenants must be >= 1, got {self.tenants}")
        if self.chunk < 1:
            bad(f"chunk must be >= 1, got {self.chunk}")
        if self.backend in ("resident", "partitioned") \
                and self.placement != "single":
            bad(f"backend={self.backend!r} is a single-filter kernel "
                f"dispatch; {self.placement!r} placements always probe "
                f"through the stacked engine")
        if self.backend == "stacked" and self.placement == "single":
            bad("backend='stacked' needs a multi-row placement "
                "(bank/tenant/store)")
        if self.tuning == "advised" and self.placement != "single":
            bad("tuning='advised' builds exact-bitmap layouts, which only "
                "the single placement's XLA path can probe (the stacked "
                "plan and the kernels are hashed-layout only)")
        if self.tuning == "adaptive" \
                and self.placement not in ("store", "tenant"):
            bad("tuning='adaptive' fits a workload model from live scans; "
                "only the store placement (retune at compaction) and the "
                "tenant placement (retune on promote) sample one")
        if self.mutability not in _MUTABILITIES:
            bad(f"mutability must be one of {_MUTABILITIES}, "
                f"got {self.mutability!r}")
        if self.mutability == "deletable" \
                and self.placement not in ("single", "store"):
            bad("mutability='deletable' needs counting lanes (single) or "
                "compaction purges (store); bank/tenant placements age out "
                "keys with mutability='ttl' instead")
        if self.mutability == "ttl" \
                and self.placement not in ("single", "tenant"):
            bad("mutability='ttl' keeps generation lanes on the resident "
                "state (single/tenant); the store expires via tombstones "
                "plus mutability='deletable' compaction purges")
        if self.generations < 2:
            bad(f"generations must be >= 2 (current + retiring), "
                f"got {self.generations}")
        if not (0.0 < self.purge_dead_frac <= 1.0):
            bad(f"purge_dead_frac must be in (0, 1], "
                f"got {self.purge_dead_frac}")
        if self.durability not in ("none", "wal"):
            bad(f"durability must be 'none' or 'wal', "
                f"got {self.durability!r}")
        if self.durability == "wal" and self.placement != "store":
            bad("durability='wal' is a store placement feature (resident "
                "filters rebuild from their source of truth instead)")
        if self.durability == "wal" and not self.wal_dir:
            bad("durability='wal' requires wal_dir")

    # -- derived sizing ---------------------------------------------------
    def resolved_bits_per_key(self) -> float:
        """bits/key from the explicit knob or the §6 model's FPR target."""
        if self.bits_per_key is not None:
            return float(self.bits_per_key)
        if self.target_fpr is None:
            return 16.0
        from .core.model import basic_range_fpr

        d = _codec_for(self.dtype).d
        n = self.n * _codec_for(self.dtype).codes_per_key
        R = 2.0 ** self.range_log2
        delta = self.delta if self.delta is not None else 7
        for bpk in range(6, 41):
            if basic_range_fpr(d, n, bpk * n, R, delta=delta) \
                    <= self.target_fpr:
                return float(bpk)
        raise ValueError(
            f"FilterSpec: no bits_per_key <= 40 reaches target_fpr="
            f"{self.target_fpr} at R=2^{self.range_log2}; relax the target "
            f"or size with bits_per_key explicitly")

    def describe(self) -> str:
        bpk = self.resolved_bits_per_key()
        return (f"FilterSpec({self.dtype}, n={self.n}, {bpk:g} b/key, "
                f"R=2^{self.range_log2}, {self.placement}/{self.backend}"
                + (f", shards={self.shards}" if self.shards > 1 else "")
                + (f", tenants={self.tenants}" if self.tenants > 1 else "")
                + ")")


# ---------------------------------------------------------------------------
# shared probe plumbing
# ---------------------------------------------------------------------------

def chunked_probe(fn, state, arrays, kdtype, chunk: int) -> np.ndarray:
    """Drive a jitted probe over host arrays in fixed-size chunks.

    The single copy of the chunking loop used by every façade handle and
    by :class:`~repro.filters.BloomRFAdapter` — one compiled shape serves
    arbitrarily large query batches.  Each chunk is four spans: the
    bounds' transfer in, the dispatch, the wait for the device, and the
    copy back (the wait is its own step so the copy times the copy)."""
    import jax
    import jax.numpy as jnp

    span = _obs_trace.span
    outs = []
    B = len(arrays[0])
    for s in range(0, B, chunk):
        with span("probe/put"):
            args = [jnp.asarray(a[s:s + chunk], kdtype) for a in arrays]
        with span("probe/dispatch"):
            out = fn(state, *args)
        with span("probe/wait"):
            jax.block_until_ready(out)
        with span("probe/fetch"):
            outs.append(np.asarray(out))
    return np.concatenate(outs) if outs else np.zeros(0, bool)


def _plan_layout(spec: FilterSpec, codec: _Codec):
    """Layout selection: tuning-free basic vs the §7 advisor."""
    from .core.tuning import advise

    n_codes = max(spec.n * codec.codes_per_key, 1)
    bpk = spec.resolved_bits_per_key()
    tuning = spec.tuning
    if tuning == "auto":
        tuning = "basic" if spec.range_log2 <= _BASIC_RANGE_LOG2 \
            else "advised"
    if tuning == "basic":
        delta = spec.delta if spec.delta is not None else min(7, codec.d)
        return basic_layout(codec.d, n_codes, bpk, delta=delta,
                            seed=spec.seed), "basic"
    return advise(codec.d, n_codes, int(n_codes * bpk),
                  R=2.0 ** spec.range_log2, point_weight=spec.point_weight,
                  seed=spec.seed).layout, "advised"


class _Handle:
    """Common surface of every façade handle."""

    def __init__(self, spec: FilterSpec, codec: _Codec):
        self.spec = spec
        self.codec = codec
        self._fpr = None        # lazy known-absent reservoir (obs/fpr.py)

    def describe(self) -> str:
        return self.spec.describe()

    def size_bits(self) -> int:
        raise NotImplementedError

    # -- observability (DESIGN.md §15) -----------------------------------
    def _fpr_sampler(self, **kw):
        """This handle's known-absent reservoir, built on first use."""
        if self._fpr is None:
            from .obs.fpr import FprSampler

            self._fpr = FprSampler(self.codec.d,
                                   seed=self.spec.seed ^ 0xB10F, **kw)
        return self._fpr

    def _observe_ranges(self, clo, chi) -> None:
        """Feed the workload sampler (range-length distribution) when the
        obs plane is on; one boolean check otherwise."""
        if _obs_metrics.enabled():
            self._fpr_sampler().observe_ranges(clo, chi)

    def _record_fpr(self, out: dict) -> dict:
        """Publish an ``observed_fpr()`` result to the registry gauges."""
        reg = _obs_metrics.registry()
        if "range_fpr" in out:
            reg.gauge("obs/fpr/observed").set(out["range_fpr"])
        if "point_fpr" in out:
            reg.gauge("obs/fpr/point").set(out["point_fpr"])
        reg.gauge("obs/fpr/range_candidates").set(
            out.get("range_candidates", 0))
        return out

    # multi-attribute sugar shared by the filter handles -----------------
    def _require_multiattr(self):
        if self.codec.name != "multiattr":
            raise TypeError(
                f"conjunctive-predicate probes need dtype='multiattr', "
                f"this filter holds {self.codec.name} keys")


class SingleFilter(_Handle):
    """One bloomRF: XLA engine path or the Pallas kernel dispatcher.

    ``backend='auto'`` uses the kernels wherever they apply (hashed layout,
    d <= 32) and the XLA engine otherwise; ``resident``/``partitioned``
    force a kernel dispatch tier; ``xla`` forces the engine.
    """

    def __init__(self, spec: FilterSpec, codec: _Codec):
        import jax

        super().__init__(spec, codec)
        require_x64(codec.d)
        layout, self.tuning = _plan_layout(spec, codec)
        backend = spec.backend
        if backend == "auto":
            # kernels only apply to hashed 32-bit layouts, and off-TPU they
            # run in interpret mode (validation, not speed): auto keeps the
            # XLA engine there and dispatches to the kernels on real TPUs
            on_tpu = jax.default_backend() == "tpu"
            backend = "kernels" if (on_tpu and codec.d <= 32
                                    and not layout.has_exact) else "xla"
        self.backend = backend
        self._bind_layout(layout)
        self.counts = None      # deletable: per-bit reference counters
        self.gens = None        # ttl: generation lanes
        self._state = self.filter.init_state()
        if spec.mutability == "deletable":
            from .core.dynamic import CountingLanes

            self.counts = CountingLanes(layout.total_bits)
        elif spec.mutability == "ttl":
            from .core.dynamic import Generations

            self.gens = Generations(self.filter.init_state, spec.generations)

    def _bind_layout(self, layout) -> None:
        """(Re)build the filter, kernel ops, and jitted entry points for
        ``layout`` — shared by ``__init__`` and :meth:`grow`."""
        import jax

        from .core.bloomrf import BloomRF
        from .kernels.ops import FilterOps

        self.layout = layout
        self.filter = BloomRF(layout, _warn=False)
        self.ops = None
        if self.backend in ("kernels", "resident", "partitioned"):
            budget = None
            if self.backend == "resident":
                budget = max(layout.total_u32, 1)
            elif self.backend == "partitioned":
                budget = 0
            self.ops = FilterOps(layout, vmem_budget_u32=budget,
                                 _warn=False)
        if self.ops is not None:
            self._point = self.ops.point
            self._range = self.ops.range
            self._insert = self.ops.insert
        else:
            self._point = jax.jit(self.filter.point)
            self._range = jax.jit(self.filter.range)
            self._insert = jax.jit(self.filter.insert)
        self._posf = jax.jit(jax.vmap(self.filter._positions_one))

    # -- state (TTL filters probe the OR-collapse of their generations) ---
    @property
    def state(self):
        return self.gens.collapsed if self.gens is not None else self._state

    @state.setter
    def state(self, value):
        if self.gens is not None:
            raise AttributeError(
                "a TTL filter's state is generation-managed; insert through "
                "insert() and age through advance_generation()")
        self._state = value

    # -- mutation ---------------------------------------------------------
    def insert(self, keys) -> None:
        codes = self.codec.encode_insert(keys)
        if _obs_metrics.enabled():
            self._fpr_sampler().observe_insert(codes)
        import jax.numpy as jnp

        kd = self.filter.kdtype
        with _obs_trace.span("facade/insert"):
            for s in range(0, len(codes), self.spec.chunk):
                cj = jnp.asarray(codes[s:s + self.spec.chunk], kd)
                if self.gens is not None:
                    self.gens.insert(self._insert, cj)
                else:
                    self._state = self._insert(self._state, cj)
                if self.counts is not None:
                    self.counts.add(np.asarray(self._posf(cj)))

    def delete(self, keys) -> None:
        """Remove previously inserted keys (``mutability='deletable'``).

        Decrements the counting lanes; bits whose counters drain to zero
        are cleared, so deleted keys stop costing false positives (up to
        counter saturation).  Deleting keys never inserted is a contract
        violation, as with any counting Bloom."""
        if self.counts is None:
            raise ValueError(
                "delete() needs FilterSpec(mutability='deletable')")
        from .core.dynamic import clear_bits

        import jax.numpy as jnp

        codes = self.codec.encode_insert(keys)
        kd = self.filter.kdtype
        for s in range(0, len(codes), self.spec.chunk):
            cj = jnp.asarray(codes[s:s + self.spec.chunk], kd)
            zeroed = self.counts.remove(np.asarray(self._posf(cj)))
            self._state = clear_bits(self._state, zeroed)

    def advance_generation(self) -> None:
        """Close the current TTL window (``mutability='ttl'``): the oldest
        generation's keys stop costing false positives; keys not
        re-inserted within ``spec.generations`` windows expire."""
        if self.gens is None:
            raise ValueError(
                "advance_generation() needs FilterSpec(mutability='ttl')")
        self.gens.advance()

    def grow(self, factor: int = 4) -> None:
        """In-place capacity promotion (``core/dynamic.py``): segment-tile
        the state onto a ``factor``-times larger layout with no key
        re-hashing — every inserted key keeps probing positive."""
        from .core.dynamic import promote_layout, promote_state

        old = self.layout
        new = promote_layout(old, factor)
        self._bind_layout(new)
        if self.gens is not None:
            self.gens = self.gens.map(
                lambda st: promote_state(st, old, new),
                zero_fn=self.filter.init_state)
        else:
            self._state = promote_state(self._state, old, new)
        if self.counts is not None:
            self.counts = self.counts.promoted(old, new)
        self.spec = dataclasses.replace(self.spec, n=self.spec.n * factor)

    # -- probes -----------------------------------------------------------
    def point(self, qs) -> np.ndarray:
        with _obs_trace.span("facade/point"):
            with _obs_trace.span("facade/encode"):
                codes = self.codec.encode_point(qs)
            return chunked_probe(self._point, self.state, [codes],
                                 self.filter.kdtype, self.spec.chunk)

    def range(self, lo, hi) -> np.ndarray:
        with _obs_trace.span("facade/range"):
            with _obs_trace.span("facade/encode"):
                clo, chi = self.codec.encode_bounds(lo, hi)
            self._observe_ranges(clo, chi)
            return chunked_probe(self._range, self.state, [clo, chi],
                                 self.filter.kdtype, self.spec.chunk)

    def observed_fpr(self) -> dict:
        """Re-probe the known-absent reservoir → live observed FPR (§15).

        Candidates are invalidated from the insert stream observed while
        observability was enabled, so enable obs before the first insert
        for an exact reservoir.  Publishes ``obs/fpr/*`` gauges."""
        s = self._fpr_sampler()
        kd = self.filter.kdtype
        return self._record_fpr(s.sample(
            point_probe=lambda ks: chunked_probe(
                self._point, self.state, [ks], kd, self.spec.chunk),
            range_probe=lambda lo, hi: chunked_probe(
                self._range, self.state, [lo, hi], kd, self.spec.chunk)))

    def range_where_b(self, b_const, a_lo, a_hi) -> np.ndarray:
        """Multiattr: ``B == b_const AND A in [a_lo, a_hi]`` via <B,A> codes."""
        self._require_multiattr()
        clo, chi = self.codec.mirrored_bounds(b_const, a_lo, a_hi)
        return chunked_probe(self._range, self.state, [clo, chi],
                             self.filter.kdtype, self.spec.chunk)

    def size_bits(self) -> int:
        return self.layout.total_bits


class BankFilter(_Handle):
    """Range-partitioned shard rows, all probed in one stacked gather."""

    def __init__(self, spec: FilterSpec, codec: _Codec):
        from .dist.filter_bank import FilterBank

        super().__init__(spec, codec)
        require_x64(codec.d)
        delta = spec.delta if spec.delta is not None else 6
        self.bank = FilterBank(codec.d, spec.shards,
                               max(spec.n * codec.codes_per_key, 1),
                               spec.resolved_bits_per_key(), delta=delta,
                               seed=spec.seed, _warn=False)
        self.state = self.bank.init_state()

    def insert(self, keys) -> None:
        codes = self.codec.encode_insert(keys)
        if _obs_metrics.enabled():
            self._fpr_sampler().observe_insert(codes)
        import jax.numpy as jnp

        with _obs_trace.span("facade/insert"):
            for s in range(0, len(codes), self.spec.chunk):
                self.state = self.bank.insert(
                    self.state, jnp.asarray(codes[s:s + self.spec.chunk],
                                            self.bank.kdtype))

    def point(self, qs) -> np.ndarray:
        with _obs_trace.span("facade/point"):
            with _obs_trace.span("facade/encode"):
                codes = self.codec.encode_point(qs)
            return chunked_probe(self.bank.point, self.state, [codes],
                                 self.bank.kdtype, self.spec.chunk)

    def range(self, lo, hi) -> np.ndarray:
        with _obs_trace.span("facade/range"):
            with _obs_trace.span("facade/encode"):
                clo, chi = self.codec.encode_bounds(lo, hi)
            self._observe_ranges(clo, chi)
            return chunked_probe(self.bank.range, self.state, [clo, chi],
                                 self.bank.kdtype, self.spec.chunk)

    def observed_fpr(self) -> dict:
        """Live observed FPR over the whole bank (see
        :meth:`SingleFilter.observed_fpr`)."""
        s = self._fpr_sampler()
        kd = self.bank.kdtype
        return self._record_fpr(s.sample(
            point_probe=lambda ks: chunked_probe(
                self.bank.point, self.state, [ks], kd, self.spec.chunk),
            range_probe=lambda lo, hi: chunked_probe(
                self.bank.range, self.state, [lo, hi], kd,
                self.spec.chunk)))

    def range_where_b(self, b_const, a_lo, a_hi) -> np.ndarray:
        self._require_multiattr()
        clo, chi = self.codec.mirrored_bounds(b_const, a_lo, a_hi)
        return chunked_probe(self.bank.range, self.state, [clo, chi],
                             self.bank.kdtype, self.spec.chunk)

    def size_bits(self) -> int:
        return self.bank.size_bits()


class TenantFilter(_Handle):
    """Per-tenant banks + the Bloofi-style meta rows, one stacked gather.

    Every probe takes a ``tenants`` vector next to the typed keys; range
    probes AND the meta verdict in by default (strictly fewer false
    positives, never a false negative)."""

    def __init__(self, spec: FilterSpec, codec: _Codec):
        from .dist.tenant_bank import TenantFilterBank

        super().__init__(spec, codec)
        require_x64(codec.d)
        delta = spec.delta if spec.delta is not None else 6
        self.bank = TenantFilterBank(
            codec.d, spec.tenants, spec.shards,
            max(spec.n * codec.codes_per_key, 1),
            spec.resolved_bits_per_key(), delta=delta, seed=spec.seed,
            _warn=False)
        self.gens = None        # ttl: generation lanes over (state, meta)
        self._fpr_tenants: dict = {}    # per-tenant reservoirs (first <= 8)
        self._wl_sampler = None         # adaptive: live scan-bounds sample
        self._promote_events: list = []  # adaptive: advised promotions
        if spec.tuning == "adaptive":
            from .obs.fpr import FprSampler

            self._wl_sampler = FprSampler(codec.d, seed=spec.seed ^ 0xAD47)
        self._state = self.bank.init_state()
        self._meta = self.bank.init_meta()
        if spec.mutability == "ttl":
            from .core.dynamic import Generations

            self.gens = Generations(
                lambda: (self.bank.init_state(), self.bank.init_meta()),
                spec.generations)

    # -- state (TTL filters probe the OR-collapse of their generations) ---
    @property
    def state(self):
        return self.gens.collapsed[0] if self.gens is not None \
            else self._state

    @property
    def meta(self):
        return self.gens.collapsed[1] if self.gens is not None \
            else self._meta

    def _tiled_tenants(self, tenants, n_codes: int):
        """Tenant ids aligned 1:1 with the encoded codes: a scalar tenant
        broadcasts over the batch, and multiattr's dual codes repeat the
        whole vector (codes are [ab..., ba...])."""
        t = np.atleast_1d(np.asarray(tenants, np.uint32))
        reps = n_codes // max(len(t), 1)
        t = np.tile(t, reps) if reps > 1 else t
        if len(t) != n_codes:
            raise ValueError(
                f"tenants ({len(t)} after broadcast) do not align with "
                f"{n_codes} encoded keys")
        return t

    #: tenants tracked with their own known-absent reservoir; per-tenant
    #: FPR telemetry over every tenant would cost O(tenants) probes per
    #: sample, so only the first few observed tenants are followed
    _MAX_FPR_TENANTS = 8

    def _tenant_sampler(self, tid: int):
        s = self._fpr_tenants.get(tid)
        if s is None and len(self._fpr_tenants) < self._MAX_FPR_TENANTS:
            from .obs.fpr import FprSampler

            s = self._fpr_tenants[tid] = FprSampler(
                self.codec.d, seed=(self.spec.seed ^ 0xB10F) + tid)
        return s

    def insert(self, tenants, keys) -> None:
        import jax.numpy as jnp

        codes = self.codec.encode_insert(keys)
        t = self._tiled_tenants(tenants, len(codes))
        if _obs_metrics.enabled():
            for tid in np.unique(t):
                s = self._tenant_sampler(int(tid))
                if s is not None:
                    s.observe_insert(codes[t == tid])
        for s in range(0, len(codes), self.spec.chunk):
            cj = jnp.asarray(codes[s:s + self.spec.chunk], self.bank.bank.kdtype)
            tj = jnp.asarray(t[s:s + self.spec.chunk])
            if self.gens is not None:
                self.gens.insert(
                    lambda sm, tt, cc: (self.bank.insert(sm[0], tt, cc),
                                        self.bank.insert_meta(sm[1], tt, cc)),
                    tj, cj)
            else:
                self._state = self.bank.insert(self._state, tj, cj)
                self._meta = self.bank.insert_meta(self._meta, tj, cj)

    def advance_generation(self) -> None:
        """Close the current TTL window (``mutability='ttl'``): tenants'
        cold keys expire after ``spec.generations`` windows without a
        re-insert and stop costing false positives — no sweeps."""
        if self.gens is None:
            raise ValueError(
                "advance_generation() needs FilterSpec(mutability='ttl')")
        self.gens.advance()

    def grow(self, factor: Optional[int] = None) -> None:
        """In-place capacity promotion of every tenant row (and the meta
        rows, and every TTL generation): segment tiling, no key re-hash.

        With ``FilterSpec(tuning='adaptive')`` and ``factor=None`` the
        promotion factor is *advised* from the sampled workload
        (``TenantFilterBank.advise_promotion``): the cost model prices
        each candidate factor's promoted layout under the observed
        range-length mix and the smallest factor that isn't clearly
        beaten wins.  Without a workload sample (or with static tuning)
        the default factor is 4."""
        from .core.dynamic import promote_state

        if factor is None:
            factor = 4
            if (self._wl_sampler is not None
                    and self._wl_sampler.workload_seen):
                from .tune import fit_workload

                wl = fit_workload(self.codec.d, sampler=self._wl_sampler)
                factor, reports = self.bank.advise_promotion(wl)
                self._promote_events.append({
                    "factor": factor,
                    "workload_seen": self._wl_sampler.workload_seen,
                    "reports": {f: r.as_dict()
                                for f, r in reports.items()},
                })
        old = self.bank
        if self.gens is not None:
            nb = old.grown(factor)
            ol, nl = old.bank.layout, nb.bank.layout
            oml, nml = old.meta_layout, nb.meta_layout
            self.gens = self.gens.map(
                lambda sm: (promote_state(sm[0], ol, nl),
                            promote_state(sm[1], oml, nml)),
                zero_fn=lambda: (nb.init_state(), nb.init_meta()))
            self.bank = nb
        else:
            self.bank, self._state, self._meta = old.promote(
                self._state, self._meta, factor)
        self.spec = dataclasses.replace(self.spec, n=self.spec.n * factor)

    def point(self, tenants, qs) -> np.ndarray:
        import jax.numpy as jnp

        out = []
        with _obs_trace.span("facade/point"):
            with _obs_trace.span("facade/encode"):
                codes = self.codec.encode_point(qs)
            t = self._tiled_tenants(tenants, len(codes))
            for s in range(0, len(codes), self.spec.chunk):
                out.append(np.asarray(self.bank.point(
                    self.state, jnp.asarray(t[s:s + self.spec.chunk]),
                    jnp.asarray(codes[s:s + self.spec.chunk],
                                self.bank.bank.kdtype))))
        return np.concatenate(out) if out else np.zeros(0, bool)

    def range(self, tenants, lo, hi, use_meta: bool = True) -> np.ndarray:
        import jax.numpy as jnp

        record_skips = _obs_metrics.enabled() and use_meta
        out = []
        with _obs_trace.span("facade/range"):
            with _obs_trace.span("facade/encode"):
                clo, chi = self.codec.encode_bounds(lo, hi)
            t = self._tiled_tenants(tenants, len(clo))
            self._observe_ranges(clo, chi)
            if self._wl_sampler is not None:
                # adaptive tuning samples regardless of the obs-plane flag
                self._wl_sampler.observe_ranges(clo, chi)
            for s in range(0, len(clo), self.spec.chunk):
                tj = jnp.asarray(t[s:s + self.spec.chunk])
                lj = jnp.asarray(clo[s:s + self.spec.chunk],
                                 self.bank.bank.kdtype)
                hj = jnp.asarray(chi[s:s + self.spec.chunk],
                                 self.bank.bank.kdtype)
                out.append(np.asarray(self.bank.range(
                    self.state, tj, lj, hj,
                    self.meta if use_meta else None)))
                if record_skips:
                    # device-scalar meta-pruning telemetry; settles at
                    # registry snapshot time, never here
                    self.bank.record_meta_skips(self.meta, tj, lj, hj)
        return np.concatenate(out) if out else np.zeros(0, bool)

    def observed_fpr(self) -> dict:
        """Per-tenant live observed FPR for every tracked tenant (§15).

        Tenants join the tracked set on their first ``insert`` while
        observability is enabled (bounded by ``_MAX_FPR_TENANTS``).
        Returns ``{tenant_id: sample_dict}`` and publishes
        ``obs/fpr/tenant/<id>`` gauges."""
        import jax.numpy as jnp

        kd = self.bank.bank.kdtype
        reg = _obs_metrics.registry()
        out = {}
        for tid, s in sorted(self._fpr_tenants.items()):
            r = s.sample(
                point_probe=lambda ks, tid=tid: np.asarray(self.bank.point(
                    self.state, jnp.full(len(ks), tid, jnp.uint32),
                    jnp.asarray(ks, kd))),
                range_probe=lambda lo, hi, tid=tid: np.asarray(
                    self.bank.range(
                        self.state, jnp.full(len(lo), tid, jnp.uint32),
                        jnp.asarray(lo, kd), jnp.asarray(hi, kd),
                        self.meta)))
            out[tid] = r
            if "range_fpr" in r:
                reg.gauge(f"obs/fpr/tenant/{tid}").set(r["range_fpr"])
        return out

    def retune_report(self) -> dict:
        """Workload sample + advised promotions (``tuning='adaptive'``)."""
        if self._wl_sampler is None:
            return {"tuning": self.spec.tuning, "promotions": []}
        from .tune import fit_workload

        return {"tuning": "adaptive",
                "workload_seen": self._wl_sampler.workload_seen,
                "promotions": list(self._promote_events),
                "workload": fit_workload(
                    self.codec.d, sampler=self._wl_sampler).to_dict()}

    def size_bits(self) -> int:
        return self.bank.size_bits()


class TypedStore(_Handle):
    """The LSM run-store behind the codec boundary: typed put/get/scan.

    Integer and float keys are bijective codes — scans decode back to the
    caller's key type exactly.  String codes are lossy (7-byte prefix +
    tail hash), so the store keeps a per-code *bucket* ``{key: value}``
    and post-filters scans by true string order: collisions cost one
    bucket, never a lost key, and scans return exactly the in-range
    entries.  Multi-attribute keys are <A,B> concatenations; ``scan``
    takes ``(a, b)`` pair bounds (a lexicographic code range), so the
    conjunctive ``A == a AND B in [b_lo, b_hi]`` predicate is
    ``scan((a, b_lo), (a, b_hi))``.
    """

    def __init__(self, spec: FilterSpec, codec: _Codec):
        from .store.store import Store, StoreConfig

        super().__init__(spec, codec)
        require_x64(codec.d)
        delta = spec.delta if spec.delta is not None else 6
        self.store = Store(StoreConfig(
            d=codec.d, memtable_limit=spec.memtable_limit,
            bits_per_key=spec.resolved_bits_per_key(),
            delta=min(delta, codec.d), fanout=spec.fanout,
            level0_runs=spec.level0_runs,
            filter_backend=spec.store_backend,
            # spec.backend='xla' pins the StackedProbe scan plane; any
            # other backend lets the store pick the fused scan megakernel
            # on TPU (kernels/store_scan.py)
            scan_backend="xla" if spec.backend == "xla" else "auto",
            seed=spec.seed,
            mutability=spec.mutability,
            purge_dead_frac=spec.purge_dead_frac,
            durability=spec.durability,
            wal_dir=spec.wal_dir,
            tuning="adaptive" if spec.tuning == "adaptive" else "static"),
            _warn=False)
        self._buckets = self.codec.name == "str"

    # -- write path -------------------------------------------------------
    def _code1(self, key) -> int:
        return int(self.codec.encode_point(key)[0])

    def put(self, key, value) -> None:
        code = self._code1(key)
        with _obs_trace.span("facade/put"):
            if self._buckets:
                bucket = dict(self.store.get(code) or {})
                bucket[key] = value
                self.store.put(code, bucket)
            else:
                self.store.put(code, value)

    def delete(self, key) -> None:
        code = self._code1(key)
        if self._buckets:
            bucket = dict(self.store.get(code) or {})
            bucket.pop(key, None)
            if bucket:
                self.store.put(code, bucket)
            else:
                self.store.delete(code)
        else:
            self.store.delete(code)

    def delete_many(self, keys) -> None:
        """Batched deletes: one memtable-flush decision for the whole
        batch (``Store.delete_many``), so eviction sweeps never cascade
        flushes/compactions mid-batch."""
        if self._buckets:
            for k in keys:      # buckets need per-key read-modify-write
                self.delete(k)
            return
        self.store.delete_many(self.codec.encode_point(keys))

    def flush(self) -> None:
        self.store.flush()

    # -- durability (FilterSpec(durability='wal', wal_dir=...)) -----------
    def checkpoint(self) -> str:
        """Publish a durable checkpoint (snapshot + manifest, WAL reset);
        see ``Store.checkpoint``.  Returns the snapshot path."""
        return self.store.checkpoint()

    def scrub(self, sample_keys: int = 64, seed: int = 0) -> dict:
        """Integrity pass over every live run (``Store.scrub``)."""
        return self.store.scrub(sample_keys=sample_keys, seed=seed)

    def close(self) -> None:
        """Release the WAL file handle (the store stays readable)."""
        self.store.close()

    # -- read path --------------------------------------------------------
    def get(self, key):
        code = self._code1(key)
        with _obs_trace.span("facade/get"):
            if self._buckets:
                bucket = self.store.get(code)
                return None if bucket is None else bucket.get(key)
            return self.store.get(code)

    def get_many(self, keys) -> list:
        if self._buckets:
            return [self.get(k) for k in keys]
        codes = self.codec.encode_point(keys)
        with _obs_trace.span("facade/get"):
            return self.store.get_many(codes)

    def scan(self, lo, hi) -> list:
        return self.scan_many([lo], [hi])[0]

    def scan_many(self, los, his) -> list:
        """Batched typed scans: one fused filter gather for the batch."""
        span = _obs_trace.span
        with span("facade/scan"):
            with span("facade/encode"):
                clo, chi = (self.codec.encode_bounds(los, his) if self._buckets
                            else self.codec.encode_bounds(np.asarray(los),
                                                          np.asarray(his)))
            self._observe_ranges(clo, chi)
            raw = self.store.scan_many(clo, chi)
            with span("facade/decode"):
                if self._buckets:
                    # typed bounds ride along: buckets post-filter by
                    # string order
                    return [self._decode_scan(rows, lo, hi)
                            for rows, lo, hi in zip(raw, los, his)]
                # iterate the store's per-query answers, NOT the caller's
                # container — multiattr column-form bounds are a (2, B)
                # array whose first axis is (a, b), so zipping the raw
                # input would truncate the batch to 2
                return [self._decode_scan(rows, None, None) for rows in raw]

    def _decode_scan(self, rows: list, lo, hi) -> list:
        if self._buckets:
            out = []
            for _, bucket in rows:
                out.extend((k, v) for k, v in bucket.items() if lo <= k <= hi)
            return sorted(out)
        if self.codec.name == "multiattr":
            return [((int(a), int(b)), v) for (a, b), v in
                    ((self.codec.decode(np.uint64(c)), v) for c, v in rows)]
        if self.codec.name in ("f32", "f64"):
            return [(float(self.codec.decode(np.uint64(c))), v)
                    for c, v in rows]
        return rows

    # -- device-resident probe plane (YCSB device driver) -----------------
    def encode_scan_bounds(self, los, his):
        """Typed scan bounds -> device code arrays in the store's key dtype
        (the operand format :meth:`scan_probe_device` takes)."""
        import jax.numpy as jnp

        clo, chi = self.codec.encode_bounds(np.asarray(los), np.asarray(his))
        kd = self.store.kdtype
        return jnp.asarray(clo, kd), jnp.asarray(chi, kd)

    def scan_probe_device(self, clo, chi):
        """Device-resident scan pruning over encoded bounds: ``(fence,
        touch)`` (B, R) bool jax arrays, no host round-trip.  Verdicts are
        at code level (for lossy string codes a touched run may still
        post-filter to empty)."""
        return self.store.scan_probe_device(clo, chi)

    # -- introspection ----------------------------------------------------
    @property
    def stats(self):
        return self.store.stats

    @property
    def n_runs(self) -> int:
        return self.store.n_runs

    def size_bits(self) -> int:
        return self.store.filter_bits()

    def retune_report(self) -> dict:
        """What the adaptive tuner has seen and done (DESIGN.md §16).

        For ``tuning='adaptive'``: the retune counter (compaction
        rebuilds that landed in a tuner-advised layout), the solver event
        log, the fitted ``bloomrf-workload/v1`` model, per-class standing
        decisions, and a model-vs-live cross-check for the largest live
        run's layout.  Static stores report ``{'tuning': 'static', ...}``
        so callers can branch without try/except."""
        tuner = self.store._tuner
        if tuner is None:
            return {"tuning": self.spec.tuning, "retunes": 0, "events": []}
        out = {"tuning": "adaptive",
               "retunes": int(self.store.stats.retunes)}
        out.update(tuner.report())
        runs = self.store.live_runs()
        if runs:
            big = max(runs, key=len)
            out["cross_check"] = tuner.cross_check(big.layout, len(big))
        return out

    # -- observability (DESIGN.md §15) ------------------------------------
    def register_obs(self, family: str = "store") -> str:
        """Register the store's :class:`StoreStats` as a metric family."""
        return self.store.register_obs(family)

    def observed_fpr(self) -> dict:
        """Live observed FPR from ground truth (§15).

        Reservoir candidates still present in the store are eliminated at
        sample time against the live key set — zero per-put overhead —
        and the survivors re-probe through the run filters; any
        ``fence & filter`` positive is a certain false positive.  Returns
        aggregate point/range FPR plus per-run range rates, and publishes
        the ``obs/fpr/*`` gauges."""
        from .store.memtable import TOMBSTONE

        store = self.store
        s = self._fpr_sampler()
        present = [np.asarray([k for k, v in store.mem.items()
                               if v is not TOMBSTONE], np.uint64)]
        present += [r.keys[~r.tombs] for r in store.live_runs()]
        s.mark_present(np.concatenate(present))
        klive = s.live_points()
        rlo, rhi = s.live_ranges()
        out = {"point_candidates": int(klive.size),
               "range_candidates": int(rlo.size),
               "workload_seen": s.workload_seen}
        if klive.size:
            fence, filt = store.probe_runs(klive, klive, point=True)
            out["point_fpr"] = float((fence & filt).any(axis=1).mean())
        if rlo.size:
            fence, filt = store.probe_runs(rlo, rhi)
            pos = fence & filt
            out["range_fpr"] = float(pos.any(axis=1).mean())
            out["range_fpr_per_run"] = [float(x) for x in pos.mean(axis=0)]
        if store._tuner is not None:
            # close the loop: the live sample is the cost model's
            # cross-check input (tune/cost.cross_check)
            store._tuner.record_observed(out)
        return self._record_fpr(out)


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------

_PLACEMENT_HANDLES = {"single": SingleFilter, "bank": BankFilter,
                      "tenant": TenantFilter, "store": TypedStore}


def open_filter(spec: FilterSpec):
    """Build the filter deployment described by ``spec``.

    Returns a :class:`SingleFilter`, :class:`BankFilter`,
    :class:`TenantFilter`, or :class:`TypedStore` according to
    ``spec.placement``; every probe surface of the returned handle encodes
    typed keys through ``core/codecs.py`` and dispatches to the
    one-fused-gather probe machinery.
    """
    if not isinstance(spec, FilterSpec):
        raise TypeError(f"open_filter takes a FilterSpec, got {type(spec)}")
    return _PLACEMENT_HANDLES[spec.placement](spec, _codec_for(spec.dtype))
