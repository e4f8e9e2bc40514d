"""Leveled compaction: run merging and filter-state merging.

Two invariants carry the whole subsystem (DESIGN.md §10):

* **Entry merge** — runs are merged newest-first; for every key only the
  newest occurrence survives (last-write-wins), and tombstones survive as
  markers unless the merge target is the store's bottom level (nothing
  older left to mask — the marker is garbage-collected).

* **Filter merge** — bloomRF state is a union-closed bitmap: the filter of
  ``A ∪ B`` built under one layout is exactly ``state_A | state_B`` (insert
  only ever ORs bits, and every probe reads through the same position
  functions).  So same-layout merges are a single ``jnp.bitwise_or`` — no
  hashing, no key replay.  Cross-layout merges (the merged run graduates to
  a larger capacity class) either *promote* each source state in place
  (segment tiling, ``core/dynamic.py`` — zero key replay; opt-in via
  ``allow_promote``) or re-insert the surviving keys through the kernels
  insert path.  Either way the merged filter covers a *superset* of the
  surviving keys (shadowed duplicates and dropped tombstones stay set), so
  the no-false-negative guarantee is preserved by construction; the
  property suite checks this against a bulk rebuild over the union.

  OR and promote merges never clear bits, so the bits of deleted (dead)
  keys accumulate and FPR drifts upward under churn.  The ``purge``
  policy caps that drift Proteus-style, at the natural rebuild point:
  when the merge's dead-entry fraction exceeds ``purge_dead_frac`` the
  filter is rebuilt from the surviving keys regardless of layout
  compatibility, washing every dead contribution out.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import FilterLayout, promote_state, promotion_factors
from ..obs import trace as _obs_trace
from .run import Run

__all__ = ["merge_sorted_runs", "merge_filter_state"]


def merge_sorted_runs(runs: List[Run], drop_tombstones: bool = False
                      ) -> Tuple[np.ndarray, list, np.ndarray]:
    """Merge runs given newest-first into (keys, vals, tombs), keys sorted.

    For duplicate keys the newest occurrence wins.  With
    ``drop_tombstones`` the surviving tombstone entries are removed
    entirely (bottom-level merges only)."""
    if not runs:
        raise ValueError("nothing to merge")
    with _obs_trace.span("compaction/merge_runs"):
        return _merge_sorted_runs(runs, drop_tombstones)


def _merge_sorted_runs(runs, drop_tombstones):
    all_keys = np.concatenate([r.keys for r in runs])
    prec = np.concatenate([np.full(len(r.keys), i, np.int64)
                           for i, r in enumerate(runs)])
    # stable pick of the newest occurrence per key: sort by (key, precedence)
    order = np.lexsort((prec, all_keys))
    ks = all_keys[order]
    first = np.concatenate([[True], ks[1:] != ks[:-1]])
    sel = order[first]
    keys = all_keys[sel]
    all_tombs = np.concatenate([r.tombs for r in runs])
    tombs = all_tombs[sel]
    flat_vals: list = []
    for r in runs:
        flat_vals.extend(r.vals)
    if drop_tombstones:
        keep = ~tombs
        keys, tombs, sel = keys[keep], tombs[keep], sel[keep]
    vals = [flat_vals[i] for i in sel]
    return keys, vals, tombs


def _state_density(run: Run) -> float:
    """Fraction of set bits in the run's filter block."""
    a = np.asarray(run.state)[..., : (run.layout.total_bits + 31) // 32]
    return float(np.unpackbits(a.view(np.uint8)).mean())


def _promotion_is_cheap(runs: List[Run], target_layout: FilterLayout,
                        n_keys: int, slack: float) -> bool:
    """Would promoting cost at most ``slack``x the per-layer density of a
    rebuild?

    A promoted segment answers queries at the *source* class's resolution
    (positions fold back mod the old segment size), so OR-ing promoted
    states unions their densities: ``1 - prod(1 - d_i)``.  A rebuild
    spreads the same keys over the full target space instead.  Estimate
    the rebuild's density from the sources' own set-bits-per-key and gate
    the promote on the ratio — promoting full filters (union density far
    above rebuild density) multiplies FPR per layer and is exactly what
    this guard rejects.
    """
    union_miss, set_bits_per_key = 1.0, []
    for r in runs:
        d = _state_density(r)
        if d >= 1.0:
            return False
        union_miss *= 1.0 - d
        set_bits_per_key.append(
            -r.layout.total_bits * np.log1p(-d) / max(len(r), 1))
    union_density = 1.0 - union_miss
    rebuild_density = 1.0 - np.exp(
        -n_keys * np.mean(set_bits_per_key) / target_layout.total_bits)
    return union_density <= slack * max(rebuild_density, 1e-9)


def merge_filter_state(runs: List[Run], target_layout: FilterLayout,
                       keys: np.ndarray,
                       build: Callable[[FilterLayout, np.ndarray], jnp.ndarray],
                       *,
                       dead_frac: float = 0.0,
                       purge_dead_frac: Optional[float] = None,
                       allow_promote: bool = False,
                       promote_density_slack: Optional[float] = None
                       ) -> Tuple[jnp.ndarray, str]:
    """Merged filter block for ``runs`` under ``target_layout``.

    Returns ``(state, how)`` with ``how`` one of:

    * ``"or"`` — every source already uses ``target_layout``: the union
      filter is the bitwise OR of the source states;
    * ``"promote"`` — every source is promotion-compatible with the target
      (``core.promotion_factors``): each state is segment-tiled in place
      and the results ORed — no key replay (``allow_promote`` only; with
      ``promote_density_slack`` set, also subject to the density guard —
      see :func:`_promotion_is_cheap`);
    * ``"rebuild"`` — surviving ``keys`` re-inserted through ``build`` (the
      kernels insert path);
    * ``"purge"`` — ``dead_frac`` exceeded ``purge_dead_frac``, forcing the
      rebuild path to wash dead keys' bits out of the filter even when an
      OR or promote merge was available.
    """
    with _obs_trace.span("compaction/merge_filters"):
        return _merge_filter_state(runs, target_layout, keys, build,
                                   dead_frac, purge_dead_frac, allow_promote,
                                   promote_density_slack)


def _merge_filter_state(runs, target_layout, keys, build, dead_frac,
                        purge_dead_frac, allow_promote,
                        promote_density_slack):
    purge = purge_dead_frac is not None and dead_frac > purge_dead_frac
    if purge:
        return build(target_layout, keys), "purge"
    if all(r.layout == target_layout and r.state is not None for r in runs):
        state = runs[0].state
        for r in runs[1:]:
            state = jnp.bitwise_or(state, r.state)
        return state, "or"
    if (allow_promote
            and all(r.state is not None for r in runs)
            and all(promotion_factors(r.layout, target_layout) is not None
                    for r in runs)
            and (promote_density_slack is None
                 or _promotion_is_cheap(runs, target_layout, len(keys),
                                        promote_density_slack))):
        state = promote_state(runs[0].state, runs[0].layout, target_layout)
        for r in runs[1:]:
            state = jnp.bitwise_or(
                state, promote_state(r.state, r.layout, target_layout))
        return state, "promote"
    return build(target_layout, keys), "rebuild"
