"""Sharded bloomRF filter bank: range-partitioned state over a device mesh.

The global key domain (``d`` bits) is range-partitioned by its top
``log2(n_shards)`` bits; shard ``s`` owns the dyadic interval
``[s << d_local, (s+1) << d_local)`` and runs an independent bloomRF over
the low ``d_local = d - log2(n_shards)`` bits.  This is exactly the
deployment shape the TPU kernels assume (kernels/ref.py, DESIGN.md §3):
a 64-bit space becomes uint32 sub-domains per shard, all lane arithmetic
stays native uint32, and each shard's state is 1/n_shards of the total.

Routing is branch-free SPMD:
  * insert — every shard computes positions for the whole key batch but only
    ORs bits of keys it owns (a masked scatter), so no all-to-all is needed;
  * point  — shard-local verdict AND ownership mask, any-reduced;
  * range  — a global [lo, hi] is clipped to each shard's interval; shards
    with a non-empty intersection answer their clipped sub-range; verdicts
    are any-reduced.  Correctness: the dyadic partition means a key is in
    [lo, hi] iff it is in exactly one shard's clipped sub-range, so the bank
    is false-negative-free whenever the per-shard filters are.

``FilterBank`` is the single-device reference (vmap over shard rows);
``ShardedFilterBank`` runs the identical per-shard math under ``shard_map``
with the state sharded over a mesh axis and verdicts all-gathered via psum —
the two are bitwise-identical by construction, which the test suite checks
on 1e5-probe workloads.

Probes route through the plan->gather->combine engine (core/engine.py).
On the single-device bank they go one step further: the shard rows are a
stack over one flat lane vector, so ``point``/``range`` probe **all**
shards at once through the multi-filter stacked plan
(``core.engine.StackedProbe``) — ONE fused gather for the whole
(batch x shard) verdict matrix, with the per-shard clipped bounds passed
as per-row bounds.  The per-shard bodies survive for the ``shard_map``
variant (each device probes only its resident rows) and stay the bitwise
reference for both paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from ..core import BloomRF, basic_layout, stacked_probe
from ..core.hashing import key_dtype_for

__all__ = ["FilterBank", "ShardedFilterBank"]


class FilterBank:
    """n_shards independent bloomRFs over a range-partitioned key domain."""

    def __init__(self, d: int, n_shards: int, n_keys: int,
                 bits_per_key: float = 16.0, delta: int = 6,
                 seed: int = 0x0B100F11, *, _warn: bool = True,
                 _layout=None):
        if _warn:
            from .._compat import warn_legacy

            warn_legacy("FilterBank(d, n_shards, ...)",
                        "dtype=..., n=..., placement='bank', shards=...")
        if n_shards < 1 or n_shards & (n_shards - 1):
            raise ValueError(f"n_shards must be a power of two, got {n_shards}")
        shard_bits = n_shards.bit_length() - 1
        if shard_bits >= d:
            raise ValueError(f"{n_shards} shards need more than d={d} bits")
        self.d = d
        self.n_shards = n_shards
        self.shard_bits = shard_bits
        self.d_local = d - shard_bits
        self.n_keys = n_keys
        self.bits_per_key = bits_per_key
        self.delta = delta
        self.seed = seed
        self.kdtype = key_dtype_for(d)
        if _layout is not None:           # in-place growth (core/dynamic.py)
            if _layout.d != self.d_local:
                raise ValueError(
                    f"_layout.d={_layout.d} != shard domain {self.d_local}")
            self.layout = _layout
        else:
            self.layout = basic_layout(self.d_local,
                                       max(n_keys // n_shards, 1),
                                       bits_per_key,
                                       delta=min(delta, self.d_local),
                                       seed=seed)
        self.filter = BloomRF(self.layout, _warn=False)
        # all shard rows probed at once: one fused gather (core/engine.py)
        self._stacked = stacked_probe(
            (self.layout,) * n_shards,
            tuple(s * self.layout.total_u32 for s in range(n_shards)))

    # -- key routing -----------------------------------------------------
    def _route(self, keys):
        """(local keys in the shard sub-domain, owning shard index)."""
        keys = jnp.asarray(keys, self.kdtype)
        if self.shard_bits == 0:  # shift by full key width is UB; shard 0 owns all
            return keys.astype(self.filter.kdtype), jnp.zeros(keys.shape,
                                                              jnp.uint32)
        shard = (keys >> self.d_local).astype(jnp.uint32)
        mask = (1 << self.d_local) - 1
        low = (keys & jnp.asarray(mask, self.kdtype)).astype(
            self.filter.kdtype)
        return low, shard

    # -- per-shard bodies (shared by vmap and shard_map paths) -----------
    def _insert_shard(self, state_row, low, owned):
        """Masked bulk insert: set positions only for owned keys."""
        f = self.filter
        pos = jax.vmap(f._positions_one)(low)                   # (B, P)
        vals = jnp.broadcast_to(owned[:, None], pos.shape).reshape(-1)
        return f.scatter_or(state_row, pos.reshape(-1), vals)

    def _point_shard(self, state_row, s_idx, low, shard):
        return self.filter.point(state_row, low) & (shard == s_idx)

    def _clip_to_shard(self, s_idx, lo_low, lo_shard, hi_low, hi_shard):
        """Clip a routed global range to shard ``s_idx``.

        Returns ``(nonempty, llo, lhi)``: whether the intersection with the
        shard's dyadic interval is non-empty, and the clipped local bounds.
        Single source of truth for the clip invariant — the tenant bank's
        meta-filter path and skip-rate accounting reuse it."""
        top = jnp.asarray((1 << self.d_local) - 1, self.filter.kdtype)
        nonempty = (s_idx >= lo_shard) & (s_idx <= hi_shard)
        llo = jnp.where(lo_shard == s_idx, lo_low, jnp.zeros_like(lo_low))
        lhi = jnp.where(hi_shard == s_idx, hi_low, top)
        return nonempty, llo, lhi

    def _range_shard(self, state_row, s_idx, lo_low, lo_shard, hi_low,
                     hi_shard):
        """Clip the global range to shard ``s_idx`` and probe the remainder."""
        nonempty, llo, lhi = self._clip_to_shard(s_idx, lo_low, lo_shard,
                                                 hi_low, hi_shard)
        return self.filter.range(state_row, llo, lhi) & nonempty

    # -- single-device reference API -------------------------------------
    def init_state(self) -> jax.Array:
        return jnp.zeros((self.n_shards, self.layout.total_u32), jnp.uint32)

    @functools.partial(jax.jit, static_argnums=0)
    def insert(self, state, keys):
        low, shard = self._route(keys)
        ids = jnp.arange(self.n_shards, dtype=jnp.uint32)
        return jax.vmap(lambda i, st: self._insert_shard(st, low, shard == i)
                        )(ids, state)

    def build(self, keys) -> jax.Array:
        return self.insert(self.init_state(), keys)

    @functools.partial(jax.jit, static_argnums=0)
    def point(self, state, qs):
        low, shard = self._route(qs)
        ids = jnp.arange(self.n_shards, dtype=jnp.uint32)
        hits = self._stacked.point_all(state.reshape(-1), low)  # (B, S)
        return (hits & (shard[:, None] == ids[None, :])).any(axis=1)

    @functools.partial(jax.jit, static_argnums=0)
    def range(self, state, lo, hi):
        lo_low, lo_shard = self._route(lo)
        hi_low, hi_shard = self._route(hi)
        ids = jnp.arange(self.n_shards, dtype=jnp.uint32)[:, None]  # (S, 1)
        nonempty, llo, lhi = self._clip_to_shard(ids, lo_low, lo_shard,
                                                 hi_low, hi_shard)  # (S, B)
        hits = self._stacked.range_all(state.reshape(-1), llo.T, lhi.T)
        return (hits & nonempty.T).any(axis=1)

    def size_bits(self) -> int:
        return self.n_shards * self.layout.total_bits


class ShardedFilterBank:
    """A :class:`FilterBank` with its shard rows laid out over a mesh axis.

    Each device owns ``n_shards / mesh.shape[axis]`` consecutive shard rows;
    probes run shard-local under ``shard_map`` and boolean verdicts are
    any-reduced with a psum all-gather.  Per-shard math is byte-for-byte the
    ``FilterBank`` body, so verdicts are bitwise identical to the
    single-device bank.
    """

    def __init__(self, bank: FilterBank, mesh: Mesh, axis: str = "data"):
        if axis not in mesh.shape:
            raise KeyError(f"mesh has no axis {axis!r}")
        n_dev = int(mesh.shape[axis])
        if bank.n_shards % n_dev:
            raise ValueError(f"{bank.n_shards} shards do not divide over "
                             f"{n_dev} devices on axis {axis!r}")
        self.bank = bank
        self.mesh = mesh
        self.axis = axis
        self.rows_per_dev = bank.n_shards // n_dev
        self.state_sharding = NamedSharding(mesh, PS(axis, None))
        spec_state = PS(axis, None)

        def local_ids():
            base = jax.lax.axis_index(axis) * self.rows_per_dev
            return (base + jnp.arange(self.rows_per_dev)).astype(jnp.uint32)

        def sm_insert(st, low, shard):
            ids = local_ids()
            return jax.vmap(lambda i, row: bank._insert_shard(
                row, low, shard == i))(ids, st)

        def sm_point(st, low, shard):
            ids = local_ids()
            hits = jax.vmap(lambda i, row: bank._point_shard(
                row, i, low, shard))(ids, st)
            local = hits.any(axis=0)
            return jax.lax.psum(local.astype(jnp.int32), axis) > 0

        def sm_range(st, lo_low, lo_shard, hi_low, hi_shard):
            ids = local_ids()
            hits = jax.vmap(lambda i, row: bank._range_shard(
                row, i, lo_low, lo_shard, hi_low, hi_shard))(ids, st)
            local = hits.any(axis=0)
            return jax.lax.psum(local.astype(jnp.int32), axis) > 0

        smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        self._insert = jax.jit(smap(
            sm_insert, in_specs=(spec_state, PS(), PS()),
            out_specs=spec_state))
        self._point = jax.jit(smap(
            sm_point, in_specs=(spec_state, PS(), PS()), out_specs=PS()))
        self._range = jax.jit(smap(
            sm_range, in_specs=(spec_state, PS(), PS(), PS(), PS()),
            out_specs=PS()))

    # -- public API (mirrors FilterBank) ---------------------------------
    def init_state(self) -> jax.Array:
        return jax.device_put(self.bank.init_state(), self.state_sharding)

    def shard_state(self, state) -> jax.Array:
        """Lay an existing (n_shards, total_u32) state out over the mesh."""
        return jax.device_put(state, self.state_sharding)

    def insert(self, state, keys):
        low, shard = self.bank._route(keys)
        return self._insert(state, low, shard)

    def build(self, keys) -> jax.Array:
        return self.insert(self.init_state(), keys)

    def point(self, state, qs):
        low, shard = self.bank._route(qs)
        return self._point(state, low, shard)

    def range(self, state, lo, hi):
        lo_low, lo_shard = self.bank._route(lo)
        hi_low, hi_shard = self.bank._route(hi)
        return self._range(state, lo_low, lo_shard, hi_low, hi_shard)
