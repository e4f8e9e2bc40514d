"""The LSM store's scan-pruning plane: one jit, one ``pallas_call``.

``Store.scan_many`` needs, per scan batch, the ``(B, R)`` fence and touch
masks over every live run: min/max fence compare, the StackedProbe plan
over all runs' filter blocks, the one fused gather, the combine, and the
touch masking.  This module runs that whole plane on the device in one
jitted call whose only kernel is the Pallas lane gather
(``kernels/gather.py``); fence compare, plan and combine are the
engine's vector arithmetic, which XLA fuses around it.

* Run rows are padded to one uniform ``rowpad`` lane width
  (:func:`build_run_stack`) and planned at bases ``r * rowpad``; rows may
  mix capacity classes (the normal LSM case) — the stacked plan groups
  equal-layout spans, and the gather serves them all at once.
* ``resident`` picks the gather tier: a stack that fits the VMEM budget
  is pinned whole; a larger one stays in HBM and each probed row is
  DMA'd into VMEM.
* Quarantined rows (filter block failed its checksum) have their filter
  verdict forced to "maybe": fence-only pruning, never a false negative.

Verdicts are bit-identical to ``StackedProbe.touch_all`` (the XLA
reference) by construction: same plan, same lanes, same words, same
combine — asserted per layout class in ``tests/test_store_scan_kernel.py``.

Layout restrictions: all rows share one key domain ``d <= 32`` and no
exact segment (the store's capacity-class ladder satisfies both by
construction); other stacks use the XLA path (``Store`` dispatches).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.engine import stacked_probe
from .gather import TILE_ALIGN, gather_lanes, probe_tile
from .rangeprobe import _check_range_kernel_layout

__all__ = ["store_scan_probe", "build_run_stack", "DEFAULT_TILE"]

DEFAULT_TILE = 256           # scan queries per gather grid step


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def build_run_stack(states) -> jax.Array:
    """Pad per-run filter states to one uniform ``(R, rowpad)`` stack.

    ``rowpad`` is a whole number of ``(8, 128)`` u32 tiles.  Zero-padding
    is safe: padded lanes sit past every row's addressable lane range, so
    no planned gather ever lands in them."""
    rowpad = _round_up(max(int(s.shape[0]) for s in states), TILE_ALIGN)
    return jnp.stack([jnp.pad(s, (0, rowpad - s.shape[0])) for s in states])


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8))
def store_scan_probe(layouts, stack: jax.Array, kmin, kmax, lo, hi,
                     tile: int = DEFAULT_TILE, resident: bool = True,
                     interpret=None, quarantine=None):
    """Fused store-scan pruning: ``(fence, touch)`` with one kernel call.

    ``layouts`` is the static per-run layout tuple, ``stack`` the
    ``uint32[R, rowpad]`` padded filter stack (:func:`build_run_stack`),
    ``kmin``/``kmax`` the per-run key fences, ``lo``/``hi`` the scan
    bounds (clamped into the ``d``-bit domain by the caller).  Returns
    ``(fence, touch)``, both ``bool[B, R]`` — exactly what
    ``StackedProbe.touch_all`` returns, with a single ``pallas_call``
    whatever the run mix (jaxpr-asserted in the test suite).

    ``resident`` pins the whole stack in VMEM; ``False`` leaves it in
    HBM and DMAs each probed row (the caller sizes the stack against
    the VMEM budget).  ``quarantine`` (optional ``(R,)``
    bool/int mask) forces those rows' filter verdicts to "maybe".
    ``interpret=None`` runs the kernel compiled on TPU and interpreted
    elsewhere.
    """
    R = len(layouts)
    if R == 0:
        raise ValueError("need at least one run row")
    d = layouts[0].d
    rowpad = int(stack.shape[1])
    for lay in layouts:
        _check_range_kernel_layout(lay)
        if lay.d != d:
            raise ValueError("store-scan rows must share one key domain")
        if lay.total_u32 > rowpad:
            raise ValueError(f"stack rowpad {rowpad} < layout lanes "
                             f"{lay.total_u32}")
    probe = stacked_probe(tuple(layouts),
                          tuple(r * rowpad for r in range(R)))
    gather = functools.partial(
        gather_lanes, resident=resident,
        tile=probe_tile(tile, probe.range_gather_width, resident),
        interpret=interpret)
    lo = jnp.atleast_1d(jnp.asarray(lo, jnp.uint32))
    hi = jnp.atleast_1d(jnp.asarray(hi, jnp.uint32))
    with jax.named_scope("bloomrf/store_scan"):
        return probe._touch_all(
            jnp.asarray(stack, jnp.uint32).reshape(-1),
            jnp.asarray(kmin, jnp.uint32), jnp.asarray(kmax, jnp.uint32),
            lo, hi, quarantine, gather=gather)
