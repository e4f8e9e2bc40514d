"""Pallas TPU kernel: bloomRF bulk insert (filter build).

The engine's position function runs in XLA and emits every bit position
the key batch sets.  The kernel keeps the filter resident in VMEM as
``(rows, 128)`` uint32 tiles for the whole grid pass: step 0 DMAs the
input state in, each step ORs one tile of positions into it with a
read-modify-write of the owning row (positions come from a blocked SMEM
operand, so rows are addressed by scalars), and the VMEM result is
written back once at the end.  TPU grid steps on a core are sequential,
so the OR needs no atomics (DESIGN.md §3).  Padding positions are -1 and
OR a zero mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import BloomRF, FilterLayout
from .gather import _UNROLL, LANES, probe_tile, resolve_interpret
from .ref import check_kernel_layout

__all__ = ["insert_resident"]

DEFAULT_TILE = 512           # keys per grid step


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _insert_kernel(pos_ref, state_hbm, out_ref, sem, *, tile: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        copy = pltpu.make_async_copy(state_hbm, out_ref, sem)
        copy.start()
        copy.wait()

    col_id = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(j, carry):
        for u in range(_UNROLL):     # in order: a row may repeat in a step
            p = pos_ref[j * _UNROLL + u]
            q = jnp.maximum(p, 0)
            bit = jnp.where(p >= 0,
                            jnp.uint32(1) << (q & 31).astype(jnp.uint32),
                            jnp.uint32(0))
            r = q >> 12                              # 4096 bits per row
            row = out_ref[pl.ds(r, 1), :]
            out_ref[pl.ds(r, 1), :] = row | jnp.where(
                col_id == ((q >> 5) & (LANES - 1)), bit, jnp.uint32(0))
        return carry

    jax.lax.fori_loop(0, tile // _UNROLL, body, 0)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def insert_resident(layout: FilterLayout, state: jax.Array, keys,
                    tile: int = DEFAULT_TILE, interpret=None):
    """OR-accumulating bulk insert with the filter resident in VMEM."""
    check_kernel_layout(layout)
    interpret = resolve_interpret(interpret)
    filt = BloomRF(layout, _warn=False)
    keys = jnp.asarray(keys, jnp.uint32)
    pos = jax.vmap(filt._positions_one)(keys).reshape(-1)
    ptile = probe_tile(tile, filt._probes_per_key)
    n = pos.shape[0]
    pos = jnp.pad(pos.astype(jnp.int32), (0, _round_up(max(n, 1), ptile) - n),
                  constant_values=-1)
    U = layout.total_u32
    rows = _round_up(U, LANES) // LANES
    state2d = jnp.pad(jnp.asarray(state, jnp.uint32),
                      (0, rows * LANES - U)).reshape(rows, LANES)
    # x64 off while tracing the kernel: Mosaic lowers 32-bit types only
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_insert_kernel, tile=ptile),
            grid=(pos.shape[0] // ptile,),
            in_specs=[pl.BlockSpec((ptile,), lambda t: (t,),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            interpret=interpret,
        )(pos, state2d)
    return out.reshape(-1)[:U]
