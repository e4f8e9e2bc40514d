"""Pallas TPU kernels: batched bloomRF point probes.

Each probe is the engine's plan -> gather -> combine (core/engine.py,
DESIGN.md §9) with the one fused gather routed through the Pallas lane
gather (``kernels/gather.py``); plan and combine stay the engine's own
XLA arithmetic, so verdicts are bit-identical to the XLA path.

* ``point_probe_resident`` — the whole filter is pinned in VMEM; the grid
  walks probe tiles.  The fast path for per-SST/per-segment filters.
* ``point_probe_partitioned`` — HBM-scale filters: the state stays in HBM
  and each probe's 512-byte row is DMA'd into VMEM (the gather's hbm
  tier).
* ``point_probe_stacked_resident`` — R same-layout rows (an LSM run
  stack) answered by the multi-filter stacked plan, one gather per call.

All kernel arithmetic is uint32 (d <= 32 sub-domains).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import BloomRF, FilterLayout
from ..core.engine import stacked_probe
from .gather import gather_lanes, probe_tile
from .ref import check_kernel_layout

__all__ = [
    "point_probe_resident",
    "point_probe_partitioned",
    "point_probe_stacked_resident",
    "DEFAULT_TILE",
]

DEFAULT_TILE = 512           # queries per grid step


def _gather(width: int, tile: int, resident: bool, interpret):
    """The engine's ``gather`` hook bound to one kernel tier."""
    return functools.partial(gather_lanes, resident=resident,
                             tile=probe_tile(tile, width, resident),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def point_probe_resident(layout: FilterLayout, state: jax.Array, keys,
                         tile: int = DEFAULT_TILE, interpret=None):
    """Batched point probe with the filter resident in VMEM."""
    check_kernel_layout(layout)
    filt = BloomRF(layout, _warn=False)
    keys = jnp.asarray(keys, jnp.uint32)
    return filt.engine.point_batched(
        state, keys, gather=_gather(filt._probes_per_key, tile, True, interpret))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def point_probe_stacked_resident(layout: FilterLayout, stack: jax.Array,
                                 keys, tile: int = DEFAULT_TILE,
                                 interpret=None):
    """Batched point probe over a ``uint32[R, total_u32]`` run stack.

    One query tile is answered against all R rows at once via the
    multi-filter stacked plan (``core.engine.StackedProbe`` — one fused
    gather per call).  Returns ``bool[B, R]``."""
    check_kernel_layout(layout)
    if layout.has_exact:
        raise ValueError("exact-layer layouts use the XLA path (ops.py)")
    R = stack.shape[0]
    probe = stacked_probe((layout,) * R,
                          tuple(r * layout.total_u32 for r in range(R)))
    keys = jnp.asarray(keys, jnp.uint32)
    width = R * BloomRF(layout, _warn=False)._probes_per_key
    return probe._point_all(stack.reshape(-1), keys,
                            gather=_gather(width, tile, True, interpret))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def point_probe_partitioned(layout: FilterLayout, state: jax.Array, keys,
                            tile: int = DEFAULT_TILE, interpret=None):
    """Batched point probe for filters too large for VMEM: the engine's
    plan and combine in XLA around the hbm-tier lane gather."""
    check_kernel_layout(layout)
    filt = BloomRF(layout, _warn=False)
    keys = jnp.asarray(keys, jnp.uint32)
    return filt.engine.point_batched(
        state, keys,
        gather=_gather(filt._probes_per_key, tile, False, interpret))
