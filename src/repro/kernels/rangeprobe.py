"""Pallas TPU kernels: batched bloomRF range probes.

Each variant runs the plan->gather->combine engine (core/engine.py,
DESIGN.md §9) with its one fused gather — the ``(B, A)`` word table,
covering-bit loads deduped against the child-word loads (4 word loads per
layer per replica) — routed through the Pallas lane gather
(``kernels/gather.py``).  Plan and combine are the engine's own XLA
arithmetic, so verdicts are bit-identical to the XLA path by construction
(same plan, same words, same combine).

* ``range_probe_resident`` — the whole filter is pinned in VMEM.
* ``range_probe_partitioned`` — HBM-scale filters, mirroring
  ``point_probe_partitioned``: the state stays in HBM, rows DMA'd per
  probe.
* ``range_probe_stacked_resident`` — R same-layout rows in VMEM, one
  gather for all rows.

Layout restrictions for the kernel paths: no exact segment (its bounded
lane scan is a dynamic while_loop — fine for XLA, not for a TPU kernel);
everything else (variable Δ, replicas, multi-segment) is supported.
Exact-layer layouts take the XLA path in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import BloomRF, FilterLayout
from ..core.engine import stacked_probe
from .probe import _gather
from .ref import check_kernel_layout

__all__ = ["range_probe_resident", "range_probe_partitioned",
           "range_probe_stacked_resident"]

DEFAULT_TILE = 512


def _check_range_kernel_layout(layout: FilterLayout) -> None:
    check_kernel_layout(layout)
    if layout.has_exact:
        raise ValueError("exact-layer layouts use the XLA path (ops.py)")


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def range_probe_resident(layout: FilterLayout, state: jax.Array, lo, hi,
                         tile: int = DEFAULT_TILE, interpret=None):
    """Batched range probe with the filter resident in VMEM."""
    _check_range_kernel_layout(layout)
    eng = BloomRF(layout, _warn=False).engine
    return eng.range_batched(
        state, jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32),
        gather=_gather(eng.range_gather_width, tile, True, interpret))


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def range_probe_stacked_resident(layout: FilterLayout, stack: jax.Array,
                                 lo, hi, tile: int = DEFAULT_TILE,
                                 interpret=None):
    """Batched range probe over a stack of R same-layout filter rows.

    ``stack`` is ``uint32[R, total_u32]`` (one row per LSM run / tenant);
    the whole stack is pinned in VMEM and each query is answered against
    **all** rows at once through the multi-filter stacked plan
    (``core.engine.StackedProbe`` — one fused gather per call).  Returns
    ``bool[B, R]``."""
    _check_range_kernel_layout(layout)
    R = stack.shape[0]
    probe = stacked_probe((layout,) * R,
                          tuple(r * layout.total_u32 for r in range(R)))
    return probe._range_all(
        stack.reshape(-1), jnp.asarray(lo, jnp.uint32),
        jnp.asarray(hi, jnp.uint32),
        gather=_gather(probe.range_gather_width, tile, True, interpret))


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def range_probe_partitioned(layout: FilterLayout, state: jax.Array, lo, hi,
                            tile: int = DEFAULT_TILE, interpret=None):
    """Batched range probe for filters too large for VMEM: the engine's
    plan and combine in XLA around the hbm-tier lane gather."""
    _check_range_kernel_layout(layout)
    eng = BloomRF(layout, _warn=False).engine
    return eng.range_batched(
        state, jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32),
        gather=_gather(eng.range_gather_width, tile, False, interpret))
