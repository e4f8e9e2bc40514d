"""Jit'd dispatch wrappers over the Pallas kernels with XLA fallbacks.

``interpret`` defaults to "interpret only off TPU": kernels run compiled
on a TPU backend and in the Pallas interpreter elsewhere (validation).

The resident/partitioned dispatch threshold is a config knob (DESIGN.md
§3): filters of up to ``vmem_budget_u32`` lanes take the VMEM-resident
kernels, larger ones the partitioned kernels.  The default comes
from the ``BLOOMRF_VMEM_BUDGET_U32`` environment variable (validated every
time it is read: non-integer or <= 0 raises a ``ValueError`` naming the
variable) and falls back to 2^21 lanes = 8 MiB.  That is half the TPU
compiler's default 16 MiB scoped-VMEM limit on a v5e core: a resident
state is one unpipelined VMEM copy, and the other half holds the
double-buffered probe tiles (2^22 lanes alone exceed the limit).  The
partitioned tier keeps the state in HBM and DMAs the probed rows.
Deployments with other VMEM sizes, or tests that want to force the
partitioned path, set the env var or pass ``vmem_budget_u32`` explicitly.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..core import BloomRF, FilterLayout
from ..core.engine import stacked_probe
from ..obs import metrics as _obs_metrics
from ..obs import trace as _obs_trace
from . import insert as _insert
from . import probe as _probe
from . import rangeprobe as _rangeprobe
from .gather import resolve_interpret
from .ref import check_kernel_layout


def _tick(tier: str) -> None:
    """Count one kernel dispatch on its tier (host int — never a tracer)."""
    if _obs_metrics.enabled():
        _obs_metrics.registry().counter(f"kernel/dispatch/{tier}").add(1)

__all__ = ["FilterOps", "DEFAULT_VMEM_BUDGET_U32", "read_vmem_budget_u32"]

#: fallback resident/partitioned threshold in uint32 lanes (8 MiB of lanes)
DEFAULT_VMEM_BUDGET_U32 = 1 << 21


def read_vmem_budget_u32() -> int:
    """The resident/partitioned threshold in uint32 lanes.

    Reads ``BLOOMRF_VMEM_BUDGET_U32`` on every call (so tests and
    deployments can flip it without re-importing) and validates it at read
    time: a value that does not parse as an integer, or is <= 0, raises a
    ``ValueError`` that names the variable."""
    raw = os.environ.get("BLOOMRF_VMEM_BUDGET_U32")
    if raw is None:
        return DEFAULT_VMEM_BUDGET_U32
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"BLOOMRF_VMEM_BUDGET_U32 must be an integer lane count, "
            f"got {raw!r}") from None
    if val <= 0:
        raise ValueError(
            f"BLOOMRF_VMEM_BUDGET_U32 must be > 0 lanes, got {val}")
    return val


class FilterOps:
    """Layout-bound kernel dispatcher.

    * small filters (<= ``vmem_budget_u32`` lanes) -> VMEM-resident kernels;
    * large filters -> partitioned point AND range probe kernels (state
      in HBM, probed rows DMA'd into VMEM); inserts take the XLA path;
    * exact-layer layouts (range) -> XLA engine path (dynamic bounded scan);
    * same-layout run *stacks* (``point_stacked``/``range_stacked``) ->
      the stacked-resident kernel while the whole (R, total_u32) stack fits
      the VMEM budget, else the XLA stacked-probe path — either way one
      fused gather per query tile across every run row.
    """

    def __init__(self, layout: FilterLayout, interpret: bool | None = None,
                 vmem_budget_u32: int | None = None, *, _warn: bool = True):
        if _warn:
            from .._compat import warn_legacy

            warn_legacy("FilterOps(layout)",
                        "dtype=..., n=..., placement='single', "
                        "backend='resident'|'partitioned'")
        check_kernel_layout(layout)
        self.layout = layout
        self.filter = BloomRF(layout, _warn=False)
        self.interpret = resolve_interpret(interpret)
        self.vmem_budget_u32 = (read_vmem_budget_u32()
                                if vmem_budget_u32 is None else vmem_budget_u32)
        self.resident = layout.total_u32 <= self.vmem_budget_u32

    # -- build ----------------------------------------------------------
    def init_state(self):
        return self.filter.init_state()

    def insert(self, state, keys):
        with _obs_trace.span("kernel/insert"):
            if self.resident:
                _tick("resident")
                return _insert.insert_resident(self.layout, state, keys,
                                               interpret=self.interpret)
            _tick("xla")
            return self.filter.insert(state, keys)  # XLA fallback

    # -- probes ----------------------------------------------------------
    def point(self, state, keys):
        with _obs_trace.span("kernel/point"):
            if self.resident:
                _tick("resident")
                return _probe.point_probe_resident(
                    self.layout, state, keys, interpret=self.interpret)
            _tick("partitioned")
            return _probe.point_probe_partitioned(
                self.layout, state, keys, interpret=self.interpret)

    def range(self, state, lo, hi):
        with _obs_trace.span("kernel/range"):
            if self.layout.has_exact:  # bounded dynamic scan: XLA engine
                _tick("xla")
                return self.filter.range(state,
                                         jnp.asarray(lo, self.filter.kdtype),
                                         jnp.asarray(hi, self.filter.kdtype))
            if self.resident:
                _tick("resident")
                return _rangeprobe.range_probe_resident(
                    self.layout, state, lo, hi, interpret=self.interpret)
            _tick("partitioned")
            return _rangeprobe.range_probe_partitioned(
                self.layout, state, lo, hi, interpret=self.interpret)

    # -- stacked-run probes (R same-layout rows, one gather per tile) ----
    def _stacked(self, n_rows: int):
        u = self.layout.total_u32
        return stacked_probe((self.layout,) * n_rows,
                             tuple(r * u for r in range(n_rows)))

    def range_stacked(self, stack, lo, hi):
        """(B, R) range verdicts over a ``uint32[R, total_u32]`` run stack."""
        if self.layout.has_exact:
            lo = jnp.asarray(lo, self.filter.kdtype)
            hi = jnp.asarray(hi, self.filter.kdtype)
            return jax.vmap(lambda row: self.filter.range(row, lo, hi),
                            out_axes=1)(stack)
        R = stack.shape[0]
        if R * self.layout.total_u32 <= self.vmem_budget_u32:
            _tick("resident")
            return _rangeprobe.range_probe_stacked_resident(
                self.layout, stack, lo, hi, interpret=self.interpret)
        _tick("xla")
        return self._stacked(R).range_all(stack.reshape(-1), lo, hi)

    def point_stacked(self, stack, keys):
        """(B, R) point verdicts over a ``uint32[R, total_u32]`` run stack."""
        if self.layout.has_exact:
            keys = jnp.asarray(keys, self.filter.kdtype)
            return jax.vmap(lambda row: self.filter.point(row, keys),
                            out_axes=1)(stack)
        R = stack.shape[0]
        if R * self.layout.total_u32 <= self.vmem_budget_u32:
            _tick("resident")
            return _probe.point_probe_stacked_resident(
                self.layout, stack, keys, interpret=self.interpret)
        _tick("xla")
        return self._stacked(R).point_all(stack.reshape(-1), keys)
