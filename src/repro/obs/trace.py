"""Span tracing: host-side timing contexts around production paths (§15).

``span(name)`` returns a context manager.  Spans have their own switch
(:func:`enable` / :func:`disable` here, or ``BLOOMRF_OBS=1`` and
``repro.obs.enable()``, which turn on the metrics plane too).  Off, a
span is a shared do-nothing singleton: one call, zero allocation.  On,
a span:

* wraps the body in ``jax.profiler.TraceAnnotation("bloomrf/<name>")``,
  so the op shows up on the device trace's clock when a profiler
  session is active (and costs ~nothing when one is not), and
* feeds the wall-clock duration into the per-op-class latency histogram
  ``obs/latency/{name}`` in the global registry when the metrics plane
  is on as well (p50/p99 come from there).

Spans alone turn on nothing else: no FPR sampler, no family
registration, no histogram, so a profiled run pays only for the
annotations.

Spans are HOST-side only.  Inside jitted functions the engine and the
store-scan kernel use ``jax.named_scope`` instead — a trace-time
annotation that adds no jaxpr equations, so the one-fused-gather and
one-``pallas_call`` invariants hold bit-for-bit with observability on
or off (pinned by ``tests/test_obs.py``).
"""
from __future__ import annotations

import time

from . import metrics as _metrics

PREFIX = "bloomrf/"

_ENABLED = _metrics.enabled()   # BLOOMRF_OBS=1 starts both switches on
_TraceAnnotation = None     # resolved on first enabled span (lazy jax)


def enabled() -> bool:
    """Are spans on?"""
    return _ENABLED


def enable() -> None:
    """Turn spans on (alone: the metrics plane keeps its own switch)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


class _NullSpan:
    """Disabled-mode span: a do-nothing context-manager singleton."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span:
    """An annotated span that also feeds ``obs/latency/{name}``."""

    __slots__ = ("name", "_t0", "_prof")

    def __init__(self, name: str):
        self.name = name
        self._t0 = 0.0
        self._prof = _annotation(name)

    def __enter__(self):
        self._prof.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur_us = (time.perf_counter() - self._t0) * 1e6
        self._prof.__exit__(*exc)
        _metrics.registry().histogram(
            f"obs/latency/{self.name}").observe(dur_us)
        return False


def _annotation(name: str):
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(PREFIX + name)


def span(name: str):
    """Span context for op-class ``name``; a no-op singleton when off."""
    if not _ENABLED:
        return NULL_SPAN
    if _metrics.enabled():
        return Span(name)
    return _annotation(name)
