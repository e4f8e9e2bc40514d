"""Multi-tenant hierarchical bloomRF filter bank.

The production workload behind the ROADMAP north-star is many independent,
growing key sets (tenants: sessions, tables, SST levels ...) behind one
range-filter service.  This module stacks one range-partitioned bloomRF bank
per tenant along a new leading tenant dim:

    state: uint32[n_tenants, n_shards, total_u32]
    meta : uint32[n_tenants, n_shards, meta_total_u32]

Three layers compose on top of :class:`~repro.dist.filter_bank.FilterBank`:

* **Tenant stacking** — routing adds an explicit tenant id next to each key;
  ownership masks become ``(shard == s) & (tenant == t)``.  Probes against a
  tenant that never inserted hit an all-zero filter row, so tenants are
  perfectly isolated (no cross-tenant false positives from an empty tenant,
  and never any false negatives).

* **Bloofi-style meta-filter** (Crainiceanu & Lemire 2015, adapted to
  bloomRF's dyadic prefixes) — per (tenant, shard) a *coarse* bloomRF built
  over the dyadic prefixes ``key >> meta_level`` of the shard's resident
  keys (``core.dyadic_prefixes``).  A range probe clips ``[lo, hi]`` to the
  shard and asks the meta-filter about the prefix range
  ``[llo >> meta_level, lhi >> meta_level]``; a negative *proves* the
  clipped sub-range empty (prefix filters are false-negative-free), so the
  shard's main filter need not be touched.  Verdicts with meta enabled are
  ``main & meta`` — identical or strictly fewer false positives — and
  :meth:`TenantFilterBank.meta_skip_stats` reports how many shard-probes the
  meta level proved empty (the memory-access saving measured by
  ``benchmarks/dist_bench.py``).

* **Read replication** — :class:`ShardedTenantFilterBank` lays tenant rows
  over a ``data`` mesh axis (like ``ShardedFilterBank``) and optionally
  replicates the whole filter state ``r``-way over a ``replica`` axis.
  Probe batches are round-robined over the replicas (``PartitionSpec`` on
  the batch dim), so read throughput scales linearly with ``r``; inserts
  are computed per replica on its sub-batch and broadcast-combined with an
  all-gather + bitwise-OR over the replica axis (the OR is the psum of the
  bit domain), leaving every replica with the identical full state.

Both classes share the per-(tenant, shard) bodies, so the shard_map variant
is bitwise-identical to the vmapped single-device reference by construction
— asserted on >= 1e5 mixed point/range probes across an 8-device
(replica x data) mesh in ``tests/test_tenant_bank.py``.

Main-filter and meta-filter probes route through the multi-filter stacked
plan (``core.engine.StackedProbe``): the single-device reference probes
every (tenant, shard) row — and, for ``range(..., meta)``, every coarse
meta row too — with ONE fused gather over the flattened row stack, the
per-shard clipped bounds (and their dyadic-prefix images for the meta
rows) riding along as per-row bounds.  The per-(tenant, shard) bodies
survive for the ``shard_map`` variants, which stay bitwise-identical to
the stacked reference.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from ..core import (BloomRF, Generations, basic_layout, dyadic_prefixes,
                    promote_layout, promote_state, stacked_probe)
from .filter_bank import FilterBank

__all__ = ["TenantFilterBank", "ShardedTenantFilterBank", "AgingTenantBank"]

_NO_TENANT = 0xFFFFFFFF  # padding sentinel tenant id: owned by nobody


class TenantFilterBank:
    """n_tenants independent :class:`FilterBank`s stacked on a leading dim."""

    def __init__(self, d: int, n_tenants: int, n_shards: int,
                 n_keys_per_tenant: int, bits_per_key: float = 16.0,
                 delta: int = 6, meta_level: Optional[int] = None,
                 meta_bits_per_prefix: float = 8.0, seed: int = 0x0B100F11,
                 *, _warn: bool = True, _layout=None, _meta_layout=None):
        if _warn:
            from .._compat import warn_legacy

            warn_legacy("TenantFilterBank(d, n_tenants, ...)",
                        "dtype=..., n=..., placement='tenant', tenants=..., "
                        "shards=...")
        if n_tenants < 1:
            raise ValueError(f"need >= 1 tenant, got {n_tenants}")
        self.bank = FilterBank(d, n_shards, n_keys_per_tenant, bits_per_key,
                               delta=delta, seed=seed, _warn=False,
                               _layout=_layout)
        self.d = d
        self.n_tenants = n_tenants
        self.n_shards = n_shards
        self.n_keys_per_tenant = n_keys_per_tenant
        self.bits_per_key = bits_per_key
        self.delta = delta
        self.meta_bits_per_prefix = meta_bits_per_prefix
        self.seed = seed
        d_local = self.bank.d_local
        if meta_level is None:
            # coarse default: a ~12-bit prefix domain per shard.  On >32-bit
            # shard domains the prefix domain must stay in the same key
            # dtype as the main rows (the meta rows join the main rows'
            # stacked one-gather plan), so it widens to 33 bits there.
            target = 12 if d_local <= 32 else 33
            meta_level = d_local - min(target, max(d_local - 1, 1))
        if not (0 < meta_level < d_local):
            raise ValueError(
                f"meta_level must be in (0, {d_local}), got {meta_level}")
        self.meta_level = meta_level
        d_meta = d_local - meta_level
        from ..core.hashing import key_dtype_for

        if key_dtype_for(d_meta) != key_dtype_for(d_local):
            raise ValueError(
                f"meta_level={meta_level} puts the {d_meta}-bit prefix "
                f"domain in a different key dtype than the {d_local}-bit "
                f"shard domain; the stacked main+meta plan needs one dtype "
                f"(keep d_meta on the same side of 32 bits as d_local)")
        if _meta_layout is not None:      # in-place growth (core/dynamic.py)
            if _meta_layout.d != d_meta:
                raise ValueError(
                    f"_meta_layout.d={_meta_layout.d} != prefix domain "
                    f"{d_meta}")
            self.meta_layout = _meta_layout
        else:
            n_prefixes = max(min(n_keys_per_tenant // n_shards,
                                 1 << min(d_meta, 24)), 1)
            self.meta_layout = basic_layout(
                d_meta, n_prefixes, meta_bits_per_prefix,
                delta=min(delta, max(d_meta, 1)), seed=seed ^ 0xB100F1)
        self.meta = BloomRF(self.meta_layout, _warn=False)
        # stacked one-gather probes over all (tenant, shard) rows; the
        # meta variant appends the coarse rows to the same flat stack
        R = n_tenants * n_shards
        U = self.bank.layout.total_u32
        Um = self.meta_layout.total_u32
        bases_main = tuple(r * U for r in range(R))
        self._stacked = stacked_probe((self.bank.layout,) * R, bases_main)
        self._stacked_meta = stacked_probe(
            (self.bank.layout,) * R + (self.meta_layout,) * R,
            bases_main + tuple(R * U + r * Um for r in range(R)))
        self._row_tenant = jnp.asarray(
            np.repeat(np.arange(n_tenants), n_shards), jnp.uint32)

    # -- per-(tenant, shard) bodies (shared with the shard_map variant) ----
    def _meta_insert_shard(self, meta_row, plow, owned):
        """Masked bulk insert of dyadic prefixes into one meta-filter row."""
        m = self.meta
        pos = jax.vmap(m._positions_one)(plow)                  # (B, P)
        vals = jnp.broadcast_to(owned[:, None], pos.shape).reshape(-1)
        return m.scatter_or(meta_row, pos.reshape(-1), vals)

    def _meta_range_shard(self, meta_row, s_idx, lo_low, lo_shard, hi_low,
                          hi_shard):
        """Coarse verdict: could shard ``s_idx`` hold any key of the clipped
        range?  A False here *proves* the clipped sub-range empty."""
        bank = self.bank
        nonempty, llo, lhi = bank._clip_to_shard(s_idx, lo_low, lo_shard,
                                                 hi_low, hi_shard)
        plo = dyadic_prefixes(llo, self.meta_level, bank.d_local)
        phi = dyadic_prefixes(lhi, self.meta_level, bank.d_local)
        return self.meta.range(meta_row, plo, phi) & nonempty

    # -- layout ----------------------------------------------------------
    def init_state(self) -> jax.Array:
        return jnp.zeros((self.n_tenants, self.n_shards,
                          self.bank.layout.total_u32), jnp.uint32)

    def init_meta(self) -> jax.Array:
        return jnp.zeros((self.n_tenants, self.n_shards,
                          self.meta_layout.total_u32), jnp.uint32)

    def _ids(self):
        return (jnp.arange(self.n_tenants, dtype=jnp.uint32),
                jnp.arange(self.n_shards, dtype=jnp.uint32))

    # -- single-device reference API --------------------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def insert(self, state, tenants, keys):
        tenants = jnp.asarray(tenants, jnp.uint32)
        low, shard = self.bank._route(keys)
        t_ids, s_ids = self._ids()

        def per_tenant(t, rows):
            return jax.vmap(lambda s, row: self.bank._insert_shard(
                row, low, (shard == s) & (tenants == t)))(s_ids, rows)

        return jax.vmap(per_tenant)(t_ids, state)

    @functools.partial(jax.jit, static_argnums=0)
    def insert_meta(self, meta, tenants, keys):
        tenants = jnp.asarray(tenants, jnp.uint32)
        low, shard = self.bank._route(keys)
        plow = dyadic_prefixes(low, self.meta_level, self.bank.d_local)
        t_ids, s_ids = self._ids()

        def per_tenant(t, rows):
            return jax.vmap(lambda s, row: self._meta_insert_shard(
                row, plow, (shard == s) & (tenants == t)))(s_ids, rows)

        return jax.vmap(per_tenant)(t_ids, meta)

    def build(self, tenants, keys) -> Tuple[jax.Array, jax.Array]:
        return (self.insert(self.init_state(), tenants, keys),
                self.insert_meta(self.init_meta(), tenants, keys))

    def _tile_rows(self, x):
        """(S, B) per-shard values -> (B, T*S) per-row values (row t*S+s
        carries shard s), matching the stacked probes' row order."""
        return jnp.tile(x.T, (1, self.n_tenants))

    @functools.partial(jax.jit, static_argnums=0)
    def point(self, state, tenants, qs):
        tenants = jnp.asarray(tenants, jnp.uint32)
        low, shard = self.bank._route(qs)
        s_row = jnp.tile(jnp.arange(self.n_shards, dtype=jnp.uint32),
                         self.n_tenants)
        own = ((shard[:, None] == s_row[None, :]) &
               (tenants[:, None] == self._row_tenant[None, :]))
        hits = self._stacked.point_all(state.reshape(-1), low)  # (B, T*S)
        return (hits & own).any(axis=1)

    @functools.partial(jax.jit, static_argnums=0)
    def range(self, state, tenants, lo, hi, meta=None):
        tenants = jnp.asarray(tenants, jnp.uint32)
        lo_low, lo_shard = self.bank._route(lo)
        hi_low, hi_shard = self.bank._route(hi)
        s_ids = jnp.arange(self.n_shards, dtype=jnp.uint32)[:, None]
        nonempty, llo, lhi = self.bank._clip_to_shard(
            s_ids, lo_low, lo_shard, hi_low, hi_shard)          # (S, B)
        own = (self._tile_rows(nonempty) &
               (tenants[:, None] == self._row_tenant[None, :]))
        if meta is None:
            hits = self._stacked.range_all(
                state.reshape(-1), self._tile_rows(llo), self._tile_rows(lhi))
            return (hits & own).any(axis=1)
        # meta rows join the same stack: main & meta in ONE fused gather
        plo = dyadic_prefixes(llo, self.meta_level, self.bank.d_local)
        phi = dyadic_prefixes(lhi, self.meta_level, self.bank.d_local)
        flat = jnp.concatenate([state.reshape(-1), meta.reshape(-1)])
        lo_all = jnp.concatenate(
            [self._tile_rows(llo), self._tile_rows(plo)], axis=1)
        hi_all = jnp.concatenate(
            [self._tile_rows(lhi), self._tile_rows(phi)], axis=1)
        hits = self._stacked_meta.range_all(flat, lo_all, hi_all)
        R = self.n_tenants * self.n_shards
        return (hits[:, :R] & hits[:, R:] & own).any(axis=1)

    @functools.partial(jax.jit, static_argnums=0)
    def meta_skip_stats(self, meta, tenants, lo, hi):
        """(candidate shard-probes, meta-skipped shard-probes) over a range
        batch.  A candidate is a (probe, shard) pair whose clipped interval
        is non-empty; it is skipped when the meta-filter proves it empty —
        each skip saves the shard's main-filter word accesses."""
        tenants = jnp.asarray(tenants, jnp.uint32)
        lo_low, lo_shard = self.bank._route(lo)
        hi_low, hi_shard = self.bank._route(hi)
        t_ids, s_ids = self._ids()

        def per_tenant(t, mrows):
            def per_shard(s, mrow):
                nonempty, _, _ = self.bank._clip_to_shard(
                    s, lo_low, lo_shard, hi_low, hi_shard)
                hit = self._meta_range_shard(mrow, s, lo_low, lo_shard,
                                             hi_low, hi_shard)
                cand = nonempty & (tenants == t)
                return cand, cand & ~hit

            return jax.vmap(per_shard)(s_ids, mrows)

        cand, skip = jax.vmap(per_tenant)(t_ids, meta)
        return cand.sum(), skip.sum()

    def record_meta_skips(self, meta, tenants, lo, hi) -> None:
        """Accumulate :meth:`meta_skip_stats` into the obs registry.

        Host helper: the jitted stats kernel is untouched; the device
        scalars it returns are handed to the ``tenant_bank/*`` counters
        without a host sync (they settle at ``snapshot()``)."""
        from ..obs import metrics as _obs_metrics

        cand, skip = self.meta_skip_stats(meta, tenants, lo, hi)
        reg = _obs_metrics.registry()
        reg.counter("tenant_bank/meta_candidates").add(cand)
        reg.counter("tenant_bank/meta_skipped").add(skip)

    def size_bits(self) -> int:
        return self.n_tenants * self.n_shards * (
            self.bank.layout.total_bits + self.meta_layout.total_bits)

    # -- in-place capacity growth (core/dynamic.py) ------------------------
    def grown(self, factor: int = 4) -> "TenantFilterBank":
        """A bank sized for ``factor`` more keys per tenant whose layouts
        are the segment-tiled promotions of this bank's — existing state
        carries over via :meth:`promote` with no key re-hashing."""
        return TenantFilterBank(
            self.d, self.n_tenants, self.n_shards,
            n_keys_per_tenant=self.n_keys_per_tenant * factor,
            bits_per_key=self.bits_per_key, delta=self.delta,
            meta_level=self.meta_level,
            meta_bits_per_prefix=self.meta_bits_per_prefix, seed=self.seed,
            _warn=False,
            _layout=promote_layout(self.bank.layout, factor),
            _meta_layout=promote_layout(self.meta_layout, factor))

    def advise_promotion(self, workload, n_current: Optional[int] = None,
                         n_target: Optional[int] = None,
                         factors: Tuple[int, ...] = (2, 4, 8)):
        """Workload-advised promotion factor (per-tenant retune, §16).

        Prices each candidate factor ``f``'s promoted layout under the
        sampled workload (``repro.tune.cost``).  Promotion tiles set bits
        ``f`` times, so a promoted segment's density equals a fresh build
        over ``f * n_current`` keys; filling the headroom to ``n_target``
        adds the difference on top — that effective key count is what the
        §7 model is scored at.  The workload's range lengths are rescaled
        to the shard-local domain (a scan's per-shard slice is
        ~``len / n_shards``).  The smallest factor with enough headroom
        wins unless a larger one at least halves the predicted mixed FPR
        (memory is ``f``-proportional; doubling it must buy a real win).

        Returns ``(factor, {factor: CostReport})``.
        """
        from ..core.dynamic import promote_layout
        from ..tune.cost import score_layout

        n_current = self.n_keys_per_tenant if n_current is None \
            else int(n_current)
        n_target = 2 * n_current if n_target is None else int(n_target)
        if n_current < 1 or n_target < n_current:
            raise ValueError(
                f"need 1 <= n_current <= n_target, got "
                f"n_current={n_current} n_target={n_target}")
        wl = workload.rescaled(
            -int(round(math.log2(self.n_shards)))) if self.n_shards > 1 \
            else workload
        reports, best = {}, None
        for f in sorted(set(int(f) for f in factors)):
            if f < 2 or self.n_keys_per_tenant * f < n_target:
                continue        # not enough headroom for the target
            try:
                lay = promote_layout(self.bank.layout, f)
            except ValueError:
                continue
            n_eff = f * n_current + (n_target - n_current)
            reports[f] = score_layout(lay, n_eff, wl)
            if best is None or \
                    reports[f].fpr_mix < 0.5 * reports[best].fpr_mix:
                best = f
        if best is None:
            raise ValueError(
                f"no promotion factor in {factors} reaches "
                f"n_target={n_target} from {self.n_keys_per_tenant} "
                f"keys/tenant")
        return best, reports

    def promote(self, state, meta, factor: int = 4
                ) -> Tuple["TenantFilterBank", jax.Array, jax.Array]:
        """Grow in place: ``(new_bank, new_state, new_meta)`` with every
        inserted key still probing positive under the new (``factor``-times
        larger) layouts — zero false negatives, no access to the original
        keys (the promotion theorem in ``core/dynamic.py``)."""
        nb = self.grown(factor)
        return (nb,
                promote_state(state, self.bank.layout, nb.bank.layout),
                promote_state(meta, self.meta_layout, nb.meta_layout))


class AgingTenantBank:
    """TTL wrapper over :class:`TenantFilterBank`: sweep-free expiry via
    generation lanes (``core.Generations``).

    Inserts land in the current generation's ``(state, meta)`` pair; every
    probe reads the OR-collapse of all generations (sound because bloomRF
    state is union-closed).  :meth:`advance` closes the TTL window — keys
    whose last insert fell out of the retained window stop costing false
    positives, with no per-key sweep and no FPR drift floor.  Reporting a
    retired key absent is the TTL contract, not a false negative; hot keys
    stay live by being re-inserted each window.
    """

    def __init__(self, bank: TenantFilterBank, n_generations: int = 4):
        self.bank = bank
        self.gens = Generations(
            lambda: (bank.init_state(), bank.init_meta()), n_generations)

    @property
    def n_generations(self) -> int:
        return self.gens.n_generations

    def insert(self, tenants, keys) -> None:
        self.gens.insert(
            lambda sm, t, k: (self.bank.insert(sm[0], t, k),
                              self.bank.insert_meta(sm[1], t, k)),
            tenants, keys)

    def point(self, tenants, qs):
        state, _ = self.gens.collapsed
        return self.bank.point(state, tenants, qs)

    def range(self, tenants, lo, hi, use_meta: bool = True):
        state, meta = self.gens.collapsed
        return self.bank.range(state, tenants, lo, hi,
                               meta if use_meta else None)

    def advance(self) -> None:
        """Retire the oldest generation's contributions."""
        self.gens.advance()

    def promoted(self, factor: int = 4) -> "AgingTenantBank":
        """Grow every generation in place to ``factor`` larger layouts."""
        nb = self.bank.grown(factor)
        ol, nl = self.bank.bank.layout, nb.bank.layout
        oml, nml = self.bank.meta_layout, nb.meta_layout
        out = AgingTenantBank.__new__(AgingTenantBank)
        out.bank = nb
        out.gens = self.gens.map(
            lambda sm: (promote_state(sm[0], ol, nl),
                        promote_state(sm[1], oml, nml)),
            zero_fn=lambda: (nb.init_state(), nb.init_meta()))
        return out

    def size_bits(self) -> int:
        return self.bank.size_bits() * self.n_generations


class ShardedTenantFilterBank:
    """A :class:`TenantFilterBank` laid out over a device mesh.

    Tenant rows are sharded over ``data_axis`` (each device owns
    ``n_tenants / mesh.shape[data_axis]`` consecutive tenants); when
    ``replica_axis`` is given, the state is additionally replicated over it
    and probe batches are split round-robin across replicas for linear read
    scaling.  Per-(tenant, shard) math is byte-for-byte the
    ``TenantFilterBank`` body, so verdicts are bitwise identical to the
    single-device bank.
    """

    def __init__(self, tbank: TenantFilterBank, mesh: Mesh,
                 data_axis: str = "data",
                 replica_axis: Optional[str] = None):
        if data_axis not in mesh.shape:
            raise KeyError(f"mesh has no axis {data_axis!r}")
        if replica_axis is not None and replica_axis not in mesh.shape:
            raise KeyError(f"mesh has no axis {replica_axis!r}")
        n_data = int(mesh.shape[data_axis])
        if tbank.n_tenants % n_data:
            raise ValueError(f"{tbank.n_tenants} tenants do not divide over "
                             f"{n_data} devices on axis {data_axis!r}")
        self.tbank = tbank
        self.mesh = mesh
        self.data_axis = data_axis
        self.replica_axis = replica_axis
        self.n_replicas = int(mesh.shape[replica_axis]) if replica_axis else 1
        self.tenants_per_dev = tbank.n_tenants // n_data
        self.state_sharding = NamedSharding(mesh, PS(data_axis, None, None))
        self._replicated = NamedSharding(mesh, PS())

        bank = tbank.bank
        tpd = self.tenants_per_dev
        r = self.n_replicas
        s_ids = jnp.arange(tbank.n_shards, dtype=jnp.uint32)
        spec_state = PS(data_axis, None, None)
        bspec = PS(replica_axis) if replica_axis is not None else PS()

        def local_tids():
            base = jax.lax.axis_index(data_axis) * tpd
            return (base + jnp.arange(tpd)).astype(jnp.uint32)

        def replica_or(new):
            """Broadcast-combine per-replica insert results: all-gather over
            the replica axis and bitwise-OR (the psum of the bit domain)."""
            if replica_axis is None:
                return new
            g = jax.lax.all_gather(new, replica_axis)
            out = g[0]
            for i in range(1, r):
                out = out | g[i]
            return out

        def sm_insert(st, low, shard, tenants):
            t_ids = local_tids()

            def per_tenant(t, rows):
                return jax.vmap(lambda s, row: bank._insert_shard(
                    row, low, (shard == s) & (tenants == t)))(s_ids, rows)

            return replica_or(jax.vmap(per_tenant)(t_ids, st))

        def sm_insert_meta(mst, plow, shard, tenants):
            t_ids = local_tids()

            def per_tenant(t, rows):
                return jax.vmap(lambda s, row: tbank._meta_insert_shard(
                    row, plow, (shard == s) & (tenants == t)))(s_ids, rows)

            return replica_or(jax.vmap(per_tenant)(t_ids, mst))

        def sm_point(st, low, shard, tenants):
            t_ids = local_tids()

            def per_tenant(t, rows):
                hits = jax.vmap(lambda s, row: bank._point_shard(
                    row, s, low, shard))(s_ids, rows)
                return hits & (tenants == t)

            local = jax.vmap(per_tenant)(t_ids, st).any(axis=(0, 1))
            return jax.lax.psum(local.astype(jnp.int32), data_axis) > 0

        def sm_range(st, lo_low, lo_shard, hi_low, hi_shard, tenants):
            t_ids = local_tids()

            def per_tenant(t, rows):
                hits = jax.vmap(lambda s, row: bank._range_shard(
                    row, s, lo_low, lo_shard, hi_low, hi_shard))(s_ids, rows)
                return hits & (tenants == t)

            local = jax.vmap(per_tenant)(t_ids, st).any(axis=(0, 1))
            return jax.lax.psum(local.astype(jnp.int32), data_axis) > 0

        def sm_range_meta(st, mst, lo_low, lo_shard, hi_low, hi_shard,
                          tenants):
            t_ids = local_tids()

            def per_tenant(t, rows, mrows):
                hits = jax.vmap(lambda s, row, mrow: bank._range_shard(
                    row, s, lo_low, lo_shard, hi_low, hi_shard)
                    & tbank._meta_range_shard(
                        mrow, s, lo_low, lo_shard, hi_low, hi_shard)
                    )(s_ids, rows, mrows)
                return hits & (tenants == t)

            local = jax.vmap(per_tenant)(t_ids, st, mst).any(axis=(0, 1))
            return jax.lax.psum(local.astype(jnp.int32), data_axis) > 0

        smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        self._insert = jax.jit(smap(
            sm_insert, in_specs=(spec_state, bspec, bspec, bspec),
            out_specs=spec_state))
        self._insert_meta = jax.jit(smap(
            sm_insert_meta, in_specs=(spec_state, bspec, bspec, bspec),
            out_specs=spec_state))
        self._point = jax.jit(smap(
            sm_point, in_specs=(spec_state, bspec, bspec, bspec),
            out_specs=bspec))
        self._range = jax.jit(smap(
            sm_range, in_specs=(spec_state,) + (bspec,) * 5,
            out_specs=bspec))
        self._range_meta = jax.jit(smap(
            sm_range_meta, in_specs=(spec_state, spec_state) + (bspec,) * 5,
            out_specs=bspec))

    # -- state placement --------------------------------------------------
    def init_state(self) -> jax.Array:
        return jax.device_put(self.tbank.init_state(), self.state_sharding)

    def init_meta(self) -> jax.Array:
        return jax.device_put(self.tbank.init_meta(), self.state_sharding)

    def shard_state(self, state) -> jax.Array:
        return jax.device_put(state, self.state_sharding)

    shard_meta = shard_state

    # -- batch round-robin over replicas ----------------------------------
    def _pad(self, tenants, arrs):
        """Pad the batch to a multiple of the replica count.  Padded slots
        carry the no-tenant sentinel, so they match no ownership mask and
        are no-ops for insert / all-False for probes."""
        n = int(tenants.shape[0])
        pad = (-n) % self.n_replicas
        if pad:
            tenants = jnp.concatenate(
                [tenants, jnp.full((pad,), _NO_TENANT, jnp.uint32)])
            arrs = [jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
                    for a in arrs]
        return tenants, arrs, n

    def _unpad(self, out, n: int):
        """Drop the replica padding.  Verdicts come back split over the
        replica axis; on a mesh with explicit axes a slice of that split
        is ambiguous, so gather them onto every device first."""
        if out.shape[0] == n:
            return out
        return jax.device_put(out, self._replicated)[:n]

    # -- public API (mirrors TenantFilterBank) -----------------------------
    def insert(self, state, tenants, keys):
        tenants = jnp.asarray(tenants, jnp.uint32)
        low, shard = self.tbank.bank._route(keys)
        tenants, (low, shard), _ = self._pad(tenants, [low, shard])
        return self._insert(state, low, shard, tenants)

    def insert_meta(self, meta, tenants, keys):
        tenants = jnp.asarray(tenants, jnp.uint32)
        low, shard = self.tbank.bank._route(keys)
        plow = dyadic_prefixes(low, self.tbank.meta_level,
                               self.tbank.bank.d_local)
        tenants, (plow, shard), _ = self._pad(tenants, [plow, shard])
        return self._insert_meta(meta, plow, shard, tenants)

    def build(self, tenants, keys) -> Tuple[jax.Array, jax.Array]:
        return (self.insert(self.init_state(), tenants, keys),
                self.insert_meta(self.init_meta(), tenants, keys))

    def point(self, state, tenants, qs):
        tenants = jnp.asarray(tenants, jnp.uint32)
        low, shard = self.tbank.bank._route(qs)
        tenants, (low, shard), n = self._pad(tenants, [low, shard])
        return self._unpad(self._point(state, low, shard, tenants), n)

    def range(self, state, tenants, lo, hi, meta=None):
        tenants = jnp.asarray(tenants, jnp.uint32)
        lo_low, lo_shard = self.tbank.bank._route(lo)
        hi_low, hi_shard = self.tbank.bank._route(hi)
        tenants, routed, n = self._pad(
            tenants, [lo_low, lo_shard, hi_low, hi_shard])
        if meta is None:
            return self._unpad(self._range(state, *routed, tenants), n)
        return self._unpad(self._range_meta(state, meta, *routed, tenants), n)
