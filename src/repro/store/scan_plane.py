"""The store's scan-pruning plane: run layouts as data over a padded stack.

``core.engine.StackedProbe`` folds every row's layout and lane base into
its program as constants, which suits the filter banks: they stack a
fixed set of rows for their whole life.  A store re-stacks on every
flush and compaction, and a program keyed by the live runs' layouts is
then a new program each time.  This plane takes the layouts as data
instead:

* **Layer tables** (:class:`RunStack`): per row, its lane base, fences
  and quarantine bit; per row and layer slot, the segment offset, word
  count, word width, level, Δ, seeds per replica, and an active mask.
  The plan runs ``layers`` slots (the most any row has); a row with fewer
  layers has its top slots masked off and starts the live/dead-path
  recurrence from its own top level, exactly as
  ``ProbeEngine.combine_range`` does for that layout alone.
* **A padded stack**: ``rows`` and ``lanes`` (:class:`PlaneShape`) are
  powers of two sized by the caller, so the compiled program changes only
  when the stack outgrows them.  Padded rows answer False everywhere.
  The stack is assembled on the host and sent with one ``device_put``,
  so re-stacking runs no device program either.

The address arithmetic is ``BloomRF._load_word`` / ``_positions_one``'s
(same ``mix``, same modulo, same lane split) and the combine is the
engine's live/dead-path algebra, so verdicts are bit-identical to
``StackedProbe`` on every live row and the filter blocks are read as
built.  Each probe is one jit with exactly one gather over the stack.
Exact-bitmap layouts are rejected (their middle scan is dynamic); the
store's capacity-class ladder never emits them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bloomrf import _FULL, _mask_u32
from ..core.hashing import mix32, mix64

__all__ = ["PlaneShape", "RunStack", "stack_runs", "range_all", "point_all",
           "touch_all"]


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class PlaneShape(NamedTuple):
    """The compiled shape of the plane: padded rows and lanes, layer and
    replica slots.  A stack that fits a shape reuses its programs."""

    rows: int
    lanes: int
    layers: int
    replicas: int

    @classmethod
    def of(cls, layouts: Sequence) -> "PlaneShape":
        """The least shape that holds ``layouts`` stacked in order (one
        guard lane past the last row: a word's second lane is always
        gathered, and used only where the word is 64 bits wide)."""
        return cls(len(layouts), sum(lay.total_u32 for lay in layouts) + 1,
                   max(lay.k for lay in layouts),
                   max(max(lay.replicas) for lay in layouts))

    def cover(self, other: "PlaneShape") -> "PlaneShape":
        """The least shape holding both, rows and lanes powers of two."""
        rows, lanes, layers, reps = (max(a, b) for a, b in zip(self, other))
        return PlaneShape(_pow2(rows), _pow2(lanes), layers, reps)


class RunStack(NamedTuple):
    """Device operands of the plane for one stack (a pytree of arrays).

    ``R`` = ``PlaneShape.rows``, ``K`` = layer slots, ``M`` = replica
    slots; key-dtype tables are uint32 for d <= 32 and uint64 above."""

    flat: jax.Array       # (lanes,) uint32: every run's filter lanes
    base: jax.Array       # (R,) int32: first lane of each row
    offbits: jax.Array    # (R, K) key dtype: bit offset of the layer's segment
    nwords: jax.Array     # (R, K) key dtype: words of the layer's segment
    wbits: jax.Array      # (R, K) key dtype: word width W = 2^(Δ-1)
    level: jax.Array      # (R, K) key dtype: the layer's level l_i
    delta: jax.Array      # (R, K) key dtype: Δ_i
    layer_on: jax.Array   # (R, K) bool: slot holds one of the row's layers
    seed: jax.Array       # (R, K, M) key dtype
    rep_on: jax.Array     # (R, K, M) bool: slot holds one of the replicas
    top: jax.Array        # (R,) key dtype: the row's top covering level
    top_lt_d: jax.Array   # (R,) bool: top level below the domain
    valid: jax.Array      # (R,) bool: a live row, not padding
    kmin: jax.Array       # (R,) key dtype: fences
    kmax: jax.Array
    quar: jax.Array       # (R,) bool: filter block quarantined


def _blank_tables(K: int, M: int, kd) -> tuple:
    """Layer tables of a slot no layer uses: a word count and width of 1
    keep its (masked) address arithmetic defined."""
    return (np.zeros(K, kd), np.ones(K, kd), np.ones(K, kd), np.zeros(K, kd),
            np.ones(K, kd), np.zeros(K, bool), np.zeros((K, M), kd),
            np.zeros((K, M), bool))


@functools.lru_cache(maxsize=256)
def _layer_tables(layout, K: int, M: int, kd) -> tuple:
    """One row's layer tables, padded to ``K`` layer and ``M`` replica
    slots (host numpy, in ``RunStack`` order)."""
    if layout.has_exact:
        raise ValueError(
            "exact-bitmap layouts cannot join the scan plane (their bounded "
            "middle scan is dynamic)")
    tabs = _blank_tables(K, M, kd)
    offbits, nwords, wbits, level, delta, layer_on, seed, rep_on = tabs
    for i in range(layout.k):
        offbits[i] = layout.seg_off_bits[layout.seg_of_layer[i]]
        nwords[i] = layout.nwords(i)
        wbits[i] = layout.word_bits(i)
        level[i] = layout.levels[i]
        delta[i] = layout.deltas[i]
        layer_on[i] = True
        for rep in range(layout.replicas[i]):
            # as ``hashing.mix``: a 32-bit domain mixes the seed's low word
            s = int(layout.seeds[i, rep])
            seed[i, rep] = s if kd == np.uint64 else s & 0xFFFFFFFF
            rep_on[i, rep] = True
    return tabs


def stack_runs(layouts: Sequence, states: Sequence, kmins, kmaxs, quar,
               shape: PlaneShape) -> RunStack:
    """Assemble a stack's operands on the host and send them in one
    ``device_put``: no device program runs.

    ``states[r]`` may be None (a quarantined run with no decodable block):
    its lanes stay zero and ``quar`` forces its verdict to "maybe"."""
    R, K, M = shape.rows, shape.layers, shape.replicas
    need = PlaneShape.of(layouts)
    if (need.rows > R or need.lanes > shape.lanes or need.layers > K
            or need.replicas > M):
        raise ValueError(f"a stack of {need} does not fit {shape}")
    kd = np.uint64 if layouts[0].d > 32 else np.uint32
    if any(lay.d != layouts[0].d for lay in layouts):
        raise ValueError("stacked rows must share one key domain")
    flat = np.zeros(shape.lanes, np.uint32)
    base = np.zeros(R, np.int32)
    tabs = [np.repeat(t[None], R, axis=0) for t in _blank_tables(K, M, kd)]
    top = np.zeros(R, kd)
    top_lt_d = np.zeros(R, bool)
    valid = np.zeros(R, bool)
    off = 0
    for r, (lay, st) in enumerate(zip(layouts, states)):
        if st is not None:
            flat[off:off + lay.total_u32] = np.asarray(st)
        base[r] = off
        off += lay.total_u32
        for t, row in zip(tabs, _layer_tables(lay, K, M, kd)):
            t[r] = row
        top[r] = lay.top_level
        top_lt_d[r] = lay.top_level < lay.d
        valid[r] = True
    n = len(layouts)

    def per_row(v, dtype, fill):
        out = np.full(R, fill, dtype)
        out[:n] = np.asarray(v, dtype)
        return out

    return jax.device_put(RunStack(
        flat, base, *tabs, top, top_lt_d, valid,
        per_row(kmins, kd, 0), per_row(kmaxs, kd, 0),
        per_row(quar, bool, False)))


# ---------------------------------------------------------------------------
# the plane's arithmetic, batched over (B, R, ...) with layouts as data
# ---------------------------------------------------------------------------

def _shr(x, s):
    """``x >> s`` for a data shift ``s``; a shift past the key width is 0
    (``BloomRF._shr``'s full shift-out)."""
    nbits = jnp.iinfo(x.dtype).bits
    return jnp.where(s >= nbits, jnp.zeros_like(x), x >> jnp.minimum(s, nbits - 1))


def _mix(x, seed):
    """``hashing.mix`` with the seed as data (same finalizer per dtype)."""
    return mix64(x ^ seed) if x.dtype == jnp.uint64 else mix32(x ^ seed)


def _words(st: RunStack, g, sh):
    """Replica-ANDed ``(lo, hi)`` lanes of planned words, as
    ``ProbeEngine._word``: ``g`` is ``(B, R, K, 4, M, 2)``, ``sh`` the
    intra-lane shift ``(B, R, K, 4, M)`` (0 for words of >= 32 bits)."""
    W = st.wbits.astype(jnp.uint32)[:, :, None]                 # (R, K, 1)
    wmask = jnp.where(W >= 32, jnp.uint32(_FULL),
                      (jnp.uint32(1) << jnp.minimum(W, 31)) - jnp.uint32(1))
    lo = jnp.full(g.shape[:-2], _FULL, jnp.uint32)
    hi = lo
    for m in range(g.shape[-2]):
        on = st.rep_on[:, :, m][:, :, None]
        lo = jnp.where(on, lo & ((g[..., m, 0] >> sh[..., m]) & wmask), lo)
        hi = jnp.where(on, hi & g[..., m, 1], hi)
    return lo, jnp.where(W == 64, hi, jnp.uint32(0))


def _children_any(parent, qlo, qhi, nonempty, wa, wb, dl, fill, W):
    """``ProbeEngine._children_any`` with Δ and W as data.  The 64-bit
    ``_mask_pair`` serves every width: for W <= 32 its high mask meets a
    zero high lane and its low mask is the narrow word's own."""
    base = parent << dl
    last = base | fill
    o_lo = (jnp.clip(qlo, base, last) - base).astype(jnp.int32)
    o_hi = (jnp.clip(qhi, base, last) - base).astype(jnp.int32)
    b = jnp.minimum(o_hi, W - 1)
    acc = ((wa[0] & _mask_u32(o_lo, jnp.minimum(b, 31)))
           | (wa[1] & _mask_u32(o_lo - 32, b - 32)))
    a2 = jnp.maximum(o_lo - W, 0)
    b2 = o_hi - W
    acc = (acc | (wb[0] & _mask_u32(a2, jnp.minimum(b2, 31)))
           | (wb[1] & _mask_u32(a2 - 32, b2 - 32)))
    return nonempty & (acc != 0)


def _cov_bit(x, li, dl, W, wa, wb):
    """``ProbeEngine._cov_bit`` with the level, Δ and W (key dtype) as
    data."""
    off = (_shr(x, li) & (W - 1)).astype(jnp.uint32)
    b = (_shr(x, li + dl - 1) & 1) != 0
    lo = jnp.where(b, wb[0], wa[0])
    hi = jnp.where(b, wb[1], wa[1])
    bit_lo = (lo >> jnp.minimum(off, 31)) & jnp.uint32(1)
    bit_hi = (hi >> (jnp.maximum(off, 32) - 32)) & jnp.uint32(1)
    return jnp.where(off < 32, bit_lo, bit_hi) != 0


def _range_verdicts(st: RunStack, lo, hi):
    """(B, R) range verdicts: plan, one gather, combine."""
    kd = st.top.dtype
    lo = jnp.atleast_1d(jnp.asarray(lo, kd))
    hi = jnp.atleast_1d(jnp.asarray(hi, kd))
    L, R = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
    B, (_, K) = L.shape[0], st.level.shape
    one = jnp.asarray(1, kd)
    with jax.named_scope("bloomrf/plan"):
        li1 = st.level + st.delta                                # (R, K)
        Lpar = _shr(L[:, None, None], li1)                       # (B, R, K)
        Rpar = _shr(R[:, None, None], li1)
        wkey = jnp.stack([Lpar << 1, (Lpar << 1) | one,
                          Rpar << 1, (Rpar << 1) | one], axis=-1)
        h = _mix(wkey[..., None], st.seed[:, :, None, :])        # (B,R,K,4,M)
        widx = h % st.nwords[:, :, None, None]
        bitoff = (st.offbits[:, :, None, None]
                  + widx * st.wbits[:, :, None, None])
        lane = (bitoff >> 5).astype(jnp.int32) + st.base[:, None, None, None]
        sh = (bitoff & 31).astype(jnp.uint32)
        lanes = jnp.stack([lane, lane + 1], axis=-1)             # (...,M,2)
    with jax.named_scope("bloomrf/gather"):
        g = st.flat[lanes.reshape(B, -1)].reshape(lanes.shape)   # the one gather
    with jax.named_scope("bloomrf/combine"):
        wlo, whi = _words(st, g, sh)                             # (B,R,K,4)
        Lb, Rb = L[:, None], R[:, None]
        lt, rt = _shr(Lb, st.top), _shr(Rb, st.top)
        split = st.top_lt_d & (lt != rt)
        # saturated top levels omitted: a middle gap of >= 1 full
        # top-level DI is untestable -> conservative positive
        result = st.top_lt_d & ((rt - lt) >= 2)
        left_alive = jnp.ones_like(split)
        right_alive = split
        for i in reversed(range(K)):
            on = st.layer_on[:, i]
            li, dl = st.level[:, i], st.delta[:, i]
            Wk = st.wbits[:, i]
            W = Wk.astype(jnp.int32)
            fill = (one << dl) - one
            bottom = i == 0
            edge = jnp.asarray(0 if bottom else 1, kd)
            Lp, Rp = _shr(Lb, li), _shr(Rb, li)
            Lpar, Rpar = _shr(Lb, li + dl), _shr(Rb, li + dl)
            w = [(wlo[:, :, i, j], whi[:, :, i, j]) for j in range(4)]

            # --- left path (doubles as the single pre-split path)
            l_end = (Lpar << dl) | fill
            l_qhi = jnp.where(split, l_end, Rp - edge)
            l_nonempty = (True if bottom else
                          jnp.where(split, Lp != l_end, (Rp - Lp) >= 2))
            hit_l = _children_any(Lpar, Lp + edge, l_qhi,
                                  l_nonempty & left_alive, w[0], w[1],
                                  dl, fill, W)
            # --- right path (only live after the split)
            r_start = Rpar << dl
            r_nonempty = True if bottom else Rp != r_start
            hit_r = _children_any(Rpar, r_start, Rp - edge,
                                  r_nonempty & right_alive, w[2], w[3],
                                  dl, fill, W)
            nxt_result = result | hit_l | hit_r
            # --- covering continuation (early-stop as mask AND)
            if not bottom:
                covL = _cov_bit(Lb, li, dl, Wk, w[0], w[1])
                covR = _cov_bit(Rb, li, dl, Wk, w[2], w[3])
                new_split = split | (Lp != Rp)
                nxt_left = left_alive & covL
                nxt_right = jnp.where(split, right_alive,
                                      left_alive & new_split) & covR
                left_alive = jnp.where(on, nxt_left, left_alive)
                right_alive = jnp.where(on, nxt_right, right_alive)
                split = jnp.where(on, new_split, split)
            result = jnp.where(on, nxt_result, result)
        return result & st.valid


def _range_all(st: RunStack, lo, hi):
    # the name the device trace gives this program (``jit__range_all``) is
    # the one the scan plane's device-time metric matches
    return _range_verdicts(st, lo, hi)


def _point_all(st: RunStack, ys):
    kd = st.top.dtype
    ys = jnp.atleast_1d(jnp.asarray(ys, kd))
    pos_dtype = jnp.int64 if kd == jnp.uint64 else jnp.int32
    with jax.named_scope("bloomrf/plan"):
        y = ys[:, None, None]
        off = _shr(y, st.level) & (st.wbits - 1)                 # (B, R, K)
        h = _mix(_shr(y, st.level + st.delta - 1)[..., None], st.seed)
        widx = h % st.nwords[..., None]                          # (B,R,K,M)
        pos = (st.offbits[..., None] + widx * st.wbits[..., None]
               + off[..., None]).astype(pos_dtype)
        lane = (pos >> 5).astype(jnp.int32) + st.base[:, None, None]
        sh = (pos & 31).astype(jnp.uint32)
    with jax.named_scope("bloomrf/gather"):
        g = st.flat[lane.reshape(ys.shape[0], -1)].reshape(lane.shape)
    with jax.named_scope("bloomrf/combine"):
        bit = ((g >> sh) & jnp.uint32(1)) == 1
        return jnp.all(bit | ~st.rep_on, axis=(2, 3)) & st.valid


def _touch_all(st: RunStack, lo, hi):
    """Fence ∧ filter in one program: ``(fence, touch)``, (B, R) bool.
    Quarantined rows answer "maybe" from their filter (fence-only
    pruning); padded rows are fenced off."""
    kd = st.top.dtype
    lo = jnp.atleast_1d(jnp.asarray(lo, kd))
    hi = jnp.atleast_1d(jnp.asarray(hi, kd))
    fence = ((hi[:, None] >= st.kmin) & (lo[:, None] <= st.kmax)
             & st.valid)
    filt = _range_verdicts(st, lo, hi) | st.quar
    return fence, fence & filt


#: (B, R) range verdicts; ``lo``/``hi`` (B,) in the key dtype
range_all = jax.jit(_range_all)
#: (B, R) point verdicts
point_all = jax.jit(_point_all)
#: (fence, touch) pair, (B, R) bool each
touch_all = jax.jit(_touch_all)
