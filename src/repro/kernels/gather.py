"""Pallas TPU kernel: the probe engine's one fused lane gather.

Every bloomRF probe is plan -> gather -> combine (``core/engine.py``,
DESIGN.md §9).  Plan and combine are vector arithmetic that XLA fuses
well; the gather ``state[lanes]`` is the memory-bound step, and the one
the TPU compiler (Mosaic) cannot lower as written: it has no 1-D gather.
This module is that gather as a kernel Mosaic does lower (DESIGN.md §3):

* the filter state is viewed as ``(rows, 128)`` uint32 tiles, so lane
  ``l`` lives at row ``l >> 7``, column ``l & 127``;
* the planned lane ids of one grid step are a blocked **SMEM** operand,
  so the kernel reads them as scalars and fetches one row per probe;
* a dynamic lane rotation moves the probed column to the probe's output
  lane, and a compare-select keeps it: 128 probes fill one lane-dense
  output row.

Two tiers share the selection step and differ in where rows come from:

* **resident** — the whole state is one VMEM operand, copied in once per
  call, and each probe's row is a dynamic-row VMEM load;
* **hbm** — states too large for VMEM stay in HBM, and each probe's row
  is its own 512-byte DMA into a VMEM row buffer: a tile's DMAs are all
  issued, then all awaited, then selected.  No sort, no bucketing.

The kernel returns exactly ``state[lanes]``, so every probe that routes
its gather here stays bit-identical to the XLA engine by construction.
All kernel arithmetic is 32-bit: the ``pallas_call`` is traced with x64
off, so a process running with ``JAX_ENABLE_X64=1`` still lowers it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_lanes", "resolve_interpret", "probe_tile", "LANES",
           "TILE_ALIGN", "MAX_PROBE_TILE", "MAX_HBM_PROBE_TILE"]

#: uint32 lanes per state row (the TPU vreg lane width)
LANES = 128
#: probe tiles are whole (8, 128) output tiles
TILE_ALIGN = 8 * LANES
#: probes per grid step; SMEM holds two such lane tiles (double-buffered)
MAX_PROBE_TILE = 32768
#: the hbm tier also holds one 512-byte row per probe in VMEM (4 MiB)
MAX_HBM_PROBE_TILE = 8192
_UNROLL = 8                  # probes per scalar-loop iteration


def resolve_interpret(interpret) -> bool:
    """``None`` -> interpret only off TPU (kernels run compiled on a chip)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def probe_tile(queries: int, width: int, resident: bool = True) -> int:
    """Probes per grid step for ``queries`` queries of ``width`` lanes."""
    t = _round_up(max(queries * width, 1), TILE_ALIGN)
    return min(t, MAX_PROBE_TILE if resident else MAX_HBM_PROBE_TILE)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _select(lane_ref, row_at, out_ref, tile: int):
    """``out[g, i] = row_at(g*128 + i)[lane & 127]`` for one probe tile."""
    col_id = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def group(g, carry):
        def probes(j, acc):
            for u in range(_UNROLL):             # unrolled by hand: Mosaic
                i = j * _UNROLL + u              # takes no partial unroll
                lane = lane_ref[g * LANES + i]
                row = row_at(g * LANES + i, lane)
                # rotate the probed column onto output lane i, keep it
                moved = pltpu.roll(row, (i - lane) & (LANES - 1), 1)
                acc = jnp.where(col_id == i, moved, acc)
            return acc

        acc = jax.lax.fori_loop(0, LANES // _UNROLL, probes,
                                jnp.zeros((1, LANES), jnp.uint32))
        out_ref[pl.ds(g, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, tile // LANES, group, 0)


def _resident_kernel(lane_ref, state_ref, out_ref, *, tile: int):
    _select(lane_ref, lambda j, lane: state_ref[pl.ds(lane >> 7, 1), :],
            out_ref, tile)


def _hbm_kernel(lane_ref, state_hbm, out_ref, rows, sem, *, tile: int):
    def row_dma(j, r):
        return pltpu.make_async_copy(state_hbm.at[pl.ds(r, 1)],
                                     rows.at[pl.ds(j, 1)], sem)

    def issue(k, carry):
        for u in range(_UNROLL):
            j = k * _UNROLL + u
            row_dma(j, lane_ref[j] >> 7).start()
        return carry

    def wait(k, carry):
        for u in range(_UNROLL):
            row_dma(k * _UNROLL + u, 0).wait()   # one row's bytes each
        return carry

    jax.lax.fori_loop(0, tile // _UNROLL, issue, 0)
    jax.lax.fori_loop(0, tile // _UNROLL, wait, 0)
    _select(lane_ref, lambda j, lane: rows[pl.ds(j, 1), :], out_ref, tile)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def gather_lanes(state: jax.Array, lanes: jax.Array, resident: bool = True,
                 tile: int = TILE_ALIGN, interpret=None) -> jax.Array:
    """``state[lanes]`` through the Pallas gather kernel.

    ``state`` is a flat ``uint32`` vector, ``lanes`` int32 lane ids of any
    shape (all in range).  ``resident`` pins the whole state in VMEM;
    otherwise it stays in HBM and rows are DMA'd per probe.  ``tile`` is
    probes per grid step (a multiple of 1024, see :func:`probe_tile`)."""
    interpret = resolve_interpret(interpret)
    cap = MAX_PROBE_TILE if resident else MAX_HBM_PROBE_TILE
    if tile % TILE_ALIGN or not 0 < tile <= cap:
        raise ValueError(f"probe tile must be a multiple of {TILE_ALIGN} "
                         f"in (0, {cap}], got {tile}")
    shape = lanes.shape
    flat = jnp.asarray(lanes, jnp.int32).reshape(-1)
    n = flat.shape[0]
    flat = jnp.pad(flat, (0, _round_up(max(n, 1), tile) - n))
    U = state.shape[0]
    rows = _round_up(U, LANES) // LANES
    state2d = jnp.pad(jnp.asarray(state, jnp.uint32),
                      (0, rows * LANES - U)).reshape(rows, LANES)
    if resident:
        kernel, state_spec, scratch = (
            _resident_kernel, pl.BlockSpec(memory_space=pltpu.VMEM), [])
    else:
        kernel, state_spec, scratch = (
            _hbm_kernel, pl.BlockSpec(memory_space=pltpu.ANY),
            [pltpu.VMEM((tile, LANES), jnp.uint32),
             pltpu.SemaphoreType.DMA(())])
    nt = flat.shape[0] // tile
    # x64 off while tracing the kernel: its index maps and loop counters
    # must be 32-bit for Mosaic even in an x64 process
    with jax.enable_x64(False), \
            jax.named_scope("bloomrf/gather/pallas_call"):
        words = pl.pallas_call(
            functools.partial(kernel, tile=tile),
            grid=(nt,),
            in_specs=[pl.BlockSpec((tile,), lambda t: (t,),
                                   memory_space=pltpu.SMEM),
                      state_spec],
            out_specs=pl.BlockSpec((tile // LANES, LANES),
                                   lambda t: (t, 0)),
            out_shape=jax.ShapeDtypeStruct((nt * tile // LANES, LANES),
                                           jnp.uint32),
            scratch_shapes=scratch,
            interpret=interpret,
        )(flat, state2d)
    return words.reshape(-1)[:n].reshape(shape)
