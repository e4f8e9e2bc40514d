"""Unified observability plane (DESIGN.md §15).

Three layers, one registry:

* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket histograms
  with device-scalar accumulation (no host sync until ``snapshot()``),
  plus registered *families* absorbing the pre-existing ad-hoc counters
  (``StoreStats``, prefix-cache hits, WAL/recovery stats) without
  breaking their field access.
* :mod:`repro.obs.trace` — host-side spans around facade / store / WAL /
  compaction ops: ``jax.profiler`` annotations named ``bloomrf/<op>``,
  on the device trace's clock when profiling, and p50/p99 latency
  histograms when the metrics plane is on too.  Spans have a switch of
  their own (``obs.trace.enable()``), so a profiled run can turn them on
  without the samplers and histograms.
* :mod:`repro.obs.fpr` — known-absent reservoirs whose periodic re-probe
  yields *live* observed FPR and the query range-length distribution
  (the Proteus-tuner workload sample).

Everything is off by default (``BLOOMRF_OBS=1`` or :func:`enable` turn on
both switches); with it off every instrumentation site is one boolean
check or one call returning a null span, and the jaxpr
invariants (one gather / one ``pallas_call``) are bit-for-bit unchanged.
``export_snapshot()`` emits the ``bloomrf-metrics/v1`` document the CI
gates consume (``benchmarks/check_gates.py``).
"""
from . import metrics as _metrics
from . import trace
from .fpr import FprSampler
from .metrics import (DEFAULT_LATENCY_BUCKETS_US, Counter, Gauge, Histogram,
                      MetricsRegistry, enabled, registry)
from .trace import span

METRICS_SCHEMA = "bloomrf-metrics/v1"


def enable() -> None:
    """Turn on the metrics plane and the spans."""
    _metrics.enable()
    trace.enable()


def disable() -> None:
    """Turn off the metrics plane and the spans."""
    _metrics.disable()
    trace.disable()


def export_snapshot(extra: dict | None = None) -> dict:
    """Materialise the registry once → a ``bloomrf-metrics/v1`` dict."""
    snap = registry().snapshot()
    if extra:
        snap.update(extra)
    return {"schema": METRICS_SCHEMA, "metrics": snap}


__all__ = [
    "DEFAULT_LATENCY_BUCKETS_US", "Counter", "FprSampler", "Gauge",
    "Histogram", "METRICS_SCHEMA", "MetricsRegistry", "disable", "enable",
    "enabled", "export_snapshot", "registry", "span", "trace",
]
