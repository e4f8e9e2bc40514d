"""Plan -> gather -> combine probe engine (DESIGN.md §9).

The reference range lookup (``BloomRF._range_one``) interleaves address
computation with state reads: per layer it issues two ``_children_any``
word-pair loads and two ``_bit_probe`` covering loads, each an independent
one-element dynamic gather that ``vmap`` turns into a separate batched
gather op — ~6 word loads per layer per query, serialised behind a long
chain of gathers.  This module refactors the probe path into three phases
with *one* fused gather per query batch:

1. **plan** — a trace-time pass over the static layout emits, per query,
   the full table of uint32 *lane* addresses needed by the two-path dyadic
   decomposition.  Two dedup facts shrink the table:

   * the covering-bit word of ``x`` at layer ``i`` is addressed by
     ``x >> (l_i + Δ_i - 1) == (parent << 1) | b`` — i.e. it is always one
     of the two child words ``parent << 1`` / ``(parent << 1) | 1`` that
     ``_children_any`` fetches for the same layer, so covering probes cost
     **zero** extra loads (6/layer -> 4/layer, times replicas);
   * replicas are flattened into the same table instead of looping loads.

   The plan also carries the query-dependent extraction metadata (intra-lane
   shifts for sub-lane words, the clipped child-offset masks' inputs) that
   the combine phase needs — all pure arithmetic, no state access.

2. **gather** — a single batched ``state[lanes]`` of shape ``(B, A)``
   fetches every word for the whole query tile at once.  ``A`` is the
   static *gather width* (``ProbeEngine.range_gather_width``); the jaxpr of
   the batched range probe contains exactly one gather over the filter
   state (asserted in ``tests/test_engine.py``).

3. **combine** — the reference live/dead path algebra evaluated purely on
   registers: child-range masks, covering-bit selects (choose child word A
   or B by the parent-side bit), and the alive-mask recurrence.  Combine is
   bit-identical to ``_range_one`` by construction — same hash formulas,
   same mask algebra, same clip/select order.

Exact-bitmap layouts: the two exact covering bits join the fused gather;
the bounded middle lane scan stays a dynamic ``while_loop`` outside the
static plan (it is the one data-dependent part of the lookup), so exact
layouts gain the dedup on every hashed layer but keep their scan.

Everything here is batched natively on ``(B,)`` query vectors — no
``vmap`` — which is what lets the Pallas kernels trace the engine directly
over a tile and what the sharded banks route through.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bloomrf import _FULL, BloomRF
from .hashing import mix

__all__ = ["ProbeEngine", "RangePlan", "PointPlan", "StackedProbe",
           "stacked_probe"]


class _Slot(NamedTuple):
    """One planned word load: column(s) in the lane table + extraction info."""

    col: int                       # first column in the (B, A) lane table
    sh: Optional[jax.Array]        # (B,) intra-lane bit shift (W < 32 only)


class RangePlan(NamedTuple):
    """Static-width address table + metadata for one range-query batch."""

    lanes: jax.Array               # (B, A) int32 — every state lane touched
    layers: tuple                  # per layer: {LA,LB,RA,RB: (slots...)}
    exact: Optional[tuple]         # ((col, sh) for L, (col, sh) for R)
    L: jax.Array                   # (B,) normalised query bounds
    R: jax.Array


class PointPlan(NamedTuple):
    lanes: jax.Array               # (B, P) int32
    sh: jax.Array                  # (B, P) uint32


class ProbeEngine:
    """Layout-bound plan/gather/combine evaluator for a :class:`BloomRF`.

    Construct via ``BloomRF.engine`` (lazily cached); the engine shares the
    filter's seeds and addressing formulas, so its verdicts are bit-identical
    to the reference scalar path (``point_reference`` / ``range_reference``).
    """

    def __init__(self, filt: BloomRF):
        self.filt = filt
        self.lay = filt.layout
        self._seeds = filt.layout.seeds
        # static plan accounting (word loads vs gathered lanes)
        loads = 0
        width = 0
        for i in range(self.lay.k):
            per_word_lanes = 2 if self.lay.word_bits(i) == 64 else 1
            loads += 4 * self.lay.replicas[i]
            width += 4 * self.lay.replicas[i] * per_word_lanes
        if self.lay.has_exact and self.lay.top_level < self.lay.d:
            loads += 2
            width += 2
        #: word loads in the static range plan (4/layer/replica + exact bits)
        self.range_word_loads = loads
        #: columns of the fused (B, A) gather — lanes, not words (W=64 -> 2)
        self.range_gather_width = width

    # ------------------------------------------------------------------
    # plan
    # ------------------------------------------------------------------
    def _word_slots(self, i: int, wordkey, cols: list) -> Tuple[_Slot, ...]:
        """Plan the replica loads of the layer-``i`` word at ``wordkey``.

        Address math mirrors ``BloomRF._load_word`` exactly (same hash, same
        modulo, same lane split) so the gathered values are the same lanes
        the reference implementation reads."""
        f, lay = self.filt, self.lay
        W = lay.word_bits(i)
        nw = lay.nwords(i)
        offbits = lay.seg_off_bits[lay.seg_of_layer[i]]
        slots = []
        for rep in range(lay.replicas[i]):
            h = mix(wordkey, self._seeds[i, rep], lay.d)
            widx = (h % np.asarray(nw, h.dtype)).astype(f.kdtype)
            bitoff = f._kd(offbits) + widx * f._kd(W)
            lane = (bitoff >> 5).astype(jnp.int32)
            col = len(cols)
            cols.append(lane)
            if W == 64:
                cols.append(lane + 1)
                slots.append(_Slot(col, None))
            elif W == 32:
                slots.append(_Slot(col, None))
            else:
                slots.append(_Slot(col, (bitoff & f._kd(31)).astype(jnp.uint32)))
        return tuple(slots)

    def _exact_slot(self, prefix, cols: list):
        f, lay = self.filt, self.lay
        pos = (f._kd(lay.exact_off_bits) + prefix).astype(f.pos_dtype)
        lane = (pos >> 5).astype(jnp.int32)
        col = len(cols)
        cols.append(lane)
        return col, (pos & 31).astype(jnp.uint32)

    def plan_range(self, lo, hi) -> RangePlan:
        """Emit the per-query lane table for the two-path decomposition.

        Per layer the plan holds exactly four words x replicas — the child
        word pairs of the left and right parents; covering bits are served
        from the same words (see module docstring), so no covering loads
        appear in the table."""
        f, lay = self.filt, self.lay
        L = f._kd(lo)
        R = f._kd(hi)
        L, R = jnp.minimum(L, R), jnp.maximum(L, R)
        cols: list = []
        layers = []
        for i in range(lay.k):
            li1 = lay.levels[i + 1]
            Lpar = f._shr(L, li1)
            Rpar = f._shr(R, li1)
            one = f._kd(1)
            layers.append({
                "LA": self._word_slots(i, Lpar << 1, cols),
                "LB": self._word_slots(i, (Lpar << 1) | one, cols),
                "RA": self._word_slots(i, Rpar << 1, cols),
                "RB": self._word_slots(i, (Rpar << 1) | one, cols),
            })
        exact = None
        if lay.has_exact and lay.top_level < lay.d:
            exact = (self._exact_slot(f._shr(L, lay.top_level), cols),
                     self._exact_slot(f._shr(R, lay.top_level), cols))
        lanes = jnp.stack(cols, axis=-1)
        return RangePlan(lanes, tuple(layers), exact, L, R)

    def plan_point(self, ys) -> PointPlan:
        pos = jax.vmap(self.filt._positions_one)(ys)        # (B, P)
        return PointPlan((pos >> 5).astype(jnp.int32),
                         (pos & 31).astype(jnp.uint32))

    # ------------------------------------------------------------------
    # gather
    # ------------------------------------------------------------------
    @staticmethod
    def gather(state: jax.Array, lanes: jax.Array) -> jax.Array:
        """The one fused load: every word for the batch in a single gather.

        The fused entry points take a ``gather`` override with this
        signature: the Pallas kernels (``kernels/gather.py``) plug their
        TPU lane gather in here and keep plan and combine as they are."""
        return state[lanes]

    # ------------------------------------------------------------------
    # combine
    # ------------------------------------------------------------------
    def _word(self, g, i: int, slots):
        """Replica-ANDed (lo, hi) lanes of one planned word (cf. _load_word)."""
        W = self.lay.word_bits(i)
        lo = jnp.uint32(_FULL)
        hi = jnp.uint32(_FULL) if W == 64 else jnp.uint32(0)
        for s in slots:
            v = g[..., s.col]
            if W == 64:
                lo = lo & v
                hi = hi & g[..., s.col + 1]
            elif W == 32:
                lo = lo & v
            else:
                lo = lo & ((v >> s.sh) & jnp.uint32((1 << W) - 1))
        return lo, hi

    def _children_any(self, i: int, parent, qlo, qhi, nonempty, wa, wb):
        """``BloomRF._children_any`` on pre-gathered word pairs (wa, wb)."""
        f, lay = self.filt, self.lay
        delta = lay.deltas[i]
        W = lay.word_bits(i)
        base = parent << delta
        last = base | f._kd((1 << delta) - 1)
        qlo_c = jnp.clip(qlo, base, last)
        qhi_c = jnp.clip(qhi, base, last)
        o_lo = (qlo_c - base).astype(jnp.int32)
        o_hi = (qhi_c - base).astype(jnp.int32)
        mAlo, mAhi = f._mask_pair(o_lo, jnp.minimum(o_hi, W - 1), W)
        acc = (wa[0] & mAlo) | (wa[1] & mAhi)
        mBlo, mBhi = f._mask_pair(jnp.maximum(o_lo - W, 0), o_hi - W, W)
        acc = acc | (wb[0] & mBlo) | (wb[1] & mBhi)
        return nonempty & (acc != 0)

    def _cov_bit(self, i: int, x, wa, wb):
        """Covering-bit probe served from the deduped child words: the word
        of ``x >> (l_i + Δ_i - 1)`` *is* child word A or B of ``x``'s parent,
        selected by the low parent-side bit — no extra load."""
        f, lay = self.filt, self.lay
        li = lay.levels[i]
        delta = lay.deltas[i]
        W = lay.word_bits(i)
        off = ((x >> li) & f._kd(W - 1)).astype(jnp.uint32)
        b = ((x >> (li + delta - 1)) & f._kd(1)) != 0
        lo = jnp.where(b, wb[0], wa[0])
        bit_lo = (lo >> jnp.minimum(off, 31)) & jnp.uint32(1)
        if W == 64:
            hi = jnp.where(b, wb[1], wa[1])
            bit_hi = (hi >> (jnp.maximum(off, 32) - 32)) & jnp.uint32(1)
            bit = jnp.where(off < 32, bit_lo, bit_hi)
        else:
            bit = bit_lo
        return bit != 0

    def combine_range(self, g: jax.Array, plan: RangePlan,
                      state: Optional[jax.Array] = None) -> jax.Array:
        """Branch-free verdicts from the gathered word matrix.

        ``state`` is only consulted for exact-bitmap layouts (the bounded
        middle scan is dynamic); hashed-only layouts combine on registers.
        """
        f, lay = self.filt, self.lay
        L, R = plan.L, plan.R
        top = lay.top_level
        false = jnp.asarray(False)

        if top >= lay.d:
            result = false
            split = false
            left_alive = jnp.asarray(True)
            right_alive = false
        else:
            lt = f._shr(L, top)
            rt = f._shr(R, top)
            split = lt != rt
            if lay.has_exact:
                if state is None:
                    raise ValueError(
                        "exact-bitmap layouts need `state` for the bounded "
                        "middle scan (combine_range(..., state=state))")
                (colL, shL), (colR, shR) = plan.exact
                covL = ((g[..., colL] >> shL) & jnp.uint32(1)) != 0
                covR = ((g[..., colR] >> shR) & jnp.uint32(1)) != 0
                mid_nonempty = (rt - lt) >= f._kd(2)
                one = f._kd(1)
                result = jax.vmap(
                    lambda a, b, ne: f._exact_range_any(state, a, b, ne)
                )(lt + one, rt - one, mid_nonempty)
                left_alive = covL
                right_alive = covR & split
            else:
                result = (rt - lt) >= f._kd(2)
                left_alive = jnp.asarray(True)
                right_alive = split

        for i in reversed(range(lay.k)):
            li = lay.levels[i]
            delta = lay.deltas[i]
            bottom = i == 0
            Lp = f._shr(L, li)
            Rp = f._shr(R, li)
            Lpar = f._shr(L, lay.levels[i + 1])
            Rpar = f._shr(R, lay.levels[i + 1])
            one = f._kd(1)
            edge = f._kd(0) if bottom else one
            wLA = self._word(g, i, plan.layers[i]["LA"])
            wLB = self._word(g, i, plan.layers[i]["LB"])
            wRA = self._word(g, i, plan.layers[i]["RA"])
            wRB = self._word(g, i, plan.layers[i]["RB"])

            # --- left path (doubles as the single pre-split path)
            l_end = (Lpar << delta) | f._kd((1 << delta) - 1)
            l_qlo = Lp + edge
            l_qhi = jnp.where(split, l_end, Rp - edge)
            if bottom:
                l_nonempty = jnp.asarray(True)
            else:
                l_nonempty = jnp.where(split, Lp != l_end,
                                       (Rp - Lp) >= f._kd(2))
            hit_l = self._children_any(i, Lpar, l_qlo, l_qhi,
                                       l_nonempty & left_alive, wLA, wLB)
            result = result | hit_l

            # --- right path (only live after the split)
            r_start = Rpar << delta
            r_qhi = Rp - edge
            r_nonempty = jnp.asarray(True) if bottom else (Rp != r_start)
            hit_r = self._children_any(i, Rpar, r_start, r_qhi,
                                       r_nonempty & right_alive, wRA, wRB)
            result = result | hit_r

            # --- covering continuation (early-stop as mask AND), bits pulled
            #     from the already-gathered child words
            if not bottom:
                covL = self._cov_bit(i, L, wLA, wLB)
                covR = self._cov_bit(i, R, wRA, wRB)
                new_split = split | (Lp != Rp)
                nxt_left = left_alive & covL
                nxt_right = jnp.where(split, right_alive, left_alive & new_split)
                nxt_right = nxt_right & covR
                left_alive, right_alive, split = nxt_left, nxt_right, new_split

        return result

    def combine_point(self, g: jax.Array, plan: PointPlan) -> jax.Array:
        bits = (g >> plan.sh) & jnp.uint32(1)
        return jnp.all(bits == 1, axis=-1)

    # ------------------------------------------------------------------
    # fused entry points
    # ------------------------------------------------------------------
    # jax.named_scope below is a trace-time annotation only: it adds NO
    # jaxpr equations, so the fused-probe invariants (and the jaxpr text
    # itself) are identical with observability on or off (tests/test_obs.py)
    def range_batched(self, state: jax.Array, lo, hi,
                      gather=None) -> jax.Array:
        with jax.named_scope("bloomrf/plan"):
            plan = self.plan_range(lo, hi)
        with jax.named_scope("bloomrf/gather"):
            g = (gather or self.gather)(state, plan.lanes)
        with jax.named_scope("bloomrf/combine"):
            return self.combine_range(
                g, plan, state=state if self.lay.has_exact else None)

    def point_batched(self, state: jax.Array, ys, gather=None) -> jax.Array:
        with jax.named_scope("bloomrf/plan"):
            plan = self.plan_point(ys)
        with jax.named_scope("bloomrf/gather"):
            g = (gather or self.gather)(state, plan.lanes)
        with jax.named_scope("bloomrf/combine"):
            return self.combine_point(g, plan)


# ---------------------------------------------------------------------------
# multi-filter stacked plan: R filter rows, ONE fused gather
# ---------------------------------------------------------------------------

class StackedProbe:
    """Probe ``R`` stacked filter rows with one fused gather per query batch.

    The rows live in a single flat ``uint32`` state vector; row ``r`` starts
    at the static lane offset ``bases[r]`` and is addressed by
    ``engines[r]`` (rows may use different layouts — an LSM store stacks
    runs of several capacity classes, the tenant bank stacks main + meta
    rows).  The plan phase emits every row's lane table with the row base
    folded in, concatenates them along the lane axis, and issues a single
    ``flat_state[lanes]`` gather of shape ``(B, sum_r A_r)``; each row's
    verdict is then combined on registers exactly as
    :meth:`ProbeEngine.combine_range` would for that row alone — verdicts
    are bit-identical to probing each row separately.

    Rows are processed as maximal *spans* of consecutive rows sharing a
    layout, so bounds are selected with slices and verdicts re-assembled
    with concatenation: the jaxpr of ``range_all``/``point_all`` contains
    exactly one gather over the filter state, whatever the row mix
    (asserted in the test suite).  Query bounds are either shared across
    rows (shape ``(B,)``) or per-row (shape ``(B, R)`` — e.g. per-shard
    clipped ranges).  Exact-bitmap layouts are rejected: their bounded
    middle scan is a dynamic loop that cannot join the static plan.
    """

    def __init__(self, engines: Tuple[ProbeEngine, ...], bases: Tuple[int, ...]):
        if not engines:
            raise ValueError("need at least one stacked row")
        if len(engines) != len(bases):
            raise ValueError(
                f"{len(engines)} engines vs {len(bases)} row bases")
        kdtype = engines[0].filt.kdtype
        for e in engines:
            if e.lay.has_exact:
                raise ValueError(
                    "exact-bitmap layouts cannot be stacked (their bounded "
                    "middle scan is dynamic); use per-row engine probes")
            if e.filt.kdtype != kdtype:
                raise ValueError("stacked rows must share one key dtype")
        self.engines = tuple(engines)
        self.bases = tuple(int(b) for b in bases)
        self.R = len(engines)
        # maximal consecutive spans sharing a layout: (engine, row0, row1)
        spans = []
        for r, e in enumerate(self.engines):
            if spans and spans[-1][0].filt.layout == e.filt.layout:
                spans[-1] = (spans[-1][0], spans[-1][1], r + 1)
            else:
                spans.append((e, r, r + 1))
        self.spans = tuple(spans)
        #: columns of the one fused (B, A) range gather, summed over rows
        self.range_gather_width = sum(
            (r1 - r0) * e.range_gather_width for e, r0, r1 in self.spans)
        self._range_jit = jax.jit(self._range_all)
        self._point_jit = jax.jit(self._point_all)
        self._touch_jit = jax.jit(self._touch_all)

    # -- bounds handling --------------------------------------------------
    def _bounds(self, a, B: int, r0: int, r1: int):
        """Span slice of shared ``(B,)`` or per-row ``(B, R)`` bounds."""
        a = jnp.asarray(a)
        if a.ndim == 1:
            return jnp.broadcast_to(a[:, None], (B, r1 - r0))
        if a.ndim != 2 or a.shape[1] != self.R:
            raise ValueError(f"bounds must be (B,) or (B, {self.R}), "
                             f"got {a.shape}")
        return a[:, r0:r1]

    # -- fused probes ------------------------------------------------------
    def _range_all(self, flat_state: jax.Array, lo, hi,
                   gather=None) -> jax.Array:
        lo = jnp.atleast_1d(jnp.asarray(lo))
        hi = jnp.atleast_1d(jnp.asarray(hi))
        B = lo.shape[0]
        # named_scope: trace-time annotation only, zero jaxpr equations —
        # the one-gather invariant is asserted with these scopes in place
        with jax.named_scope("bloomrf/plan"):
            parts, plans = [], []
            for e, r0, r1 in self.spans:
                plan = e.plan_range(self._bounds(lo, B, r0, r1),
                                    self._bounds(hi, B, r0, r1))
                # row bases fold in as python-int adds (no captured constant
                # arrays — the Pallas stacked kernels trace this function)
                shifted = jnp.stack(
                    [plan.lanes[:, i, :] + self.bases[r0 + i]
                     for i in range(r1 - r0)], axis=1)
                parts.append(shifted.reshape(B, -1))
                plans.append(plan)
        with jax.named_scope("bloomrf/gather"):
            g = (gather or ProbeEngine.gather)(      # the one gather
                flat_state, jnp.concatenate(parts, axis=-1))
        with jax.named_scope("bloomrf/combine"):
            out, off = [], 0
            for (e, r0, r1), plan in zip(self.spans, plans):
                G, A = r1 - r0, e.range_gather_width
                gg = g[:, off:off + G * A].reshape(B, G, A)
                off += G * A
                out.append(e.combine_range(gg, plan))
            return jnp.concatenate(out, axis=-1)          # (B, R)

    def _point_all(self, flat_state: jax.Array, ys,
                   gather=None) -> jax.Array:
        ys = jnp.atleast_1d(jnp.asarray(ys))
        B = ys.shape[0]
        with jax.named_scope("bloomrf/plan"):
            parts, plans = [], []
            for e, r0, r1 in self.spans:
                plan = e.plan_point(ys)                   # lanes/sh (B, P)
                shifted = jnp.stack(
                    [plan.lanes + self.bases[r] for r in range(r0, r1)],
                    axis=1)
                parts.append(shifted.reshape(B, -1))
                plans.append(plan)
        with jax.named_scope("bloomrf/gather"):
            g = (gather or ProbeEngine.gather)(      # the one gather
                flat_state, jnp.concatenate(parts, axis=-1))
        with jax.named_scope("bloomrf/combine"):
            out, off = [], 0
            for (e, r0, r1), plan in zip(self.spans, plans):
                G, P = r1 - r0, plan.lanes.shape[-1]
                gg = g[:, off:off + G * P].reshape(B, G, P)
                off += G * P
                bits = (gg >> plan.sh[:, None, :]) & jnp.uint32(1)
                out.append(jnp.all(bits == 1, axis=-1))
            return jnp.concatenate(out, axis=-1)          # (B, R)

    def _touch_all(self, flat_state: jax.Array, kmin, kmax, lo, hi,
                   quarantine=None, gather=None):
        """Fence-fused range probe: the full store scan-pruning plane.

        ``kmin``/``kmax`` are per-row key fences (shape ``(R,)``, key
        dtype).  Returns ``(fence, touch)``, both ``(B, R)`` bool:
        ``fence`` is interval overlap with the row's key range and
        ``touch = fence & filter_verdict`` — the data blocks a scan must
        actually read.  ``quarantine`` (optional ``(R,)`` bool) marks rows
        whose filter block failed its checksum (DESIGN.md §14): their
        filter verdict is forced to "maybe", degrading that row to
        fence-only pruning — a corrupted filter must never skip a run it
        might cover (that would be a false negative).  This is the
        XLA-exact reference the store-scan Pallas megakernel
        (``kernels/store_scan.py``) is bit-identical to; everything
        (fence compare, the one fused gather, combine, masking) stays on
        device in one jit.  Bounds must already be clamped into the
        filters' key domain (the store dispatch clamps and zeroes rows
        whose query lies entirely above the domain)."""
        lo = jnp.atleast_1d(jnp.asarray(lo))
        hi = jnp.atleast_1d(jnp.asarray(hi))
        kmin = jnp.asarray(kmin, lo.dtype)
        kmax = jnp.asarray(kmax, lo.dtype)
        fence = ((hi[:, None] >= kmin[None, :])
                 & (lo[:, None] <= kmax[None, :]))
        filt = self._range_all(flat_state, lo, hi, gather)
        if quarantine is not None:
            filt = filt | jnp.asarray(quarantine, bool)[None, :]
        return fence, fence & filt

    def range_all(self, flat_state: jax.Array, lo, hi) -> jax.Array:
        """(B, R) bool: per-row range verdicts from one fused gather."""
        return self._range_jit(flat_state, lo, hi)

    def touch_all(self, flat_state: jax.Array, kmin, kmax, lo, hi,
                  quarantine=None):
        """(fence, touch) ``(B, R)`` bool pair — see :meth:`_touch_all`."""
        return self._touch_jit(flat_state, kmin, kmax, lo, hi, quarantine)

    def point_all(self, flat_state: jax.Array, ys) -> jax.Array:
        """(B, R) bool: per-row point verdicts from one fused gather."""
        return self._point_jit(flat_state, ys)


@functools.lru_cache(maxsize=None)
def _filter_for_layout(layout) -> BloomRF:
    return BloomRF(layout, _warn=False)


@functools.lru_cache(maxsize=None)
def stacked_probe(layouts: tuple, bases: tuple) -> StackedProbe:
    """Cached :class:`StackedProbe` for a row stack described by layouts.

    Layouts are hashable frozen dataclasses, so call sites that re-stack the
    same row mix (an LSM store after every flush/compaction, a bank per
    construction) share one probe instance — and with it the jit cache of
    the fused probe functions."""
    engines = tuple(_filter_for_layout(lay).engine for lay in layouts)
    return StackedProbe(engines, bases)
