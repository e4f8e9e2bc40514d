"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
touches no jax device state.  Mesh shapes (TPU v5e):

* single pod:  (data=16, model=16)          — 256 chips
* multi-pod:   (pod=2, data=16, model=16)   — 512 chips

Logical use: batch/FSDP over ("pod","data"); TP/EP/SP over "model"; the
"pod" axis can alternatively drive the pipeline utilities (dist/pipeline.py).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_smoke_mesh"]


def _mesh(shape, axes):
    # Auto axes: the models place activations with sharding constraints,
    # which jax.make_mesh's default Explicit axes refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh():
    """A tiny mesh over however many devices the test process has."""
    n = len(jax.devices())
    if n >= 4:
        return _mesh((2, n // 2), ("data", "model"))
    return _mesh((1, n), ("data", "model"))
