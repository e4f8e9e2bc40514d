"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Nothing runs here: each test lowers a kernel with ``interpret=False`` and
compiles it for a described (not attached) v5e chip, which catches what
interpret mode cannot — an op Mosaic does not lower, a block shape off the
TPU tiling, a 64-bit type, more VMEM than the scoped limit.  The topology
is described inside a fixture, never at import, and the persistent
compilation cache is off around these compiles (an entry compiled for a
described chip cannot be read back without one)."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.api import FilterSpec, _codec_for, _plan_layout
from repro.core import basic_layout
from repro.core.bloomrf import BloomRF
from repro.kernels import (DEFAULT_VMEM_BUDGET_U32, insert_resident,
                           point_probe_partitioned, range_probe_partitioned,
                           range_probe_resident, range_probe_stacked_resident,
                           store_scan_probe)
from repro.kernels.store_scan import DEFAULT_TILE as SCAN_TILE

BIG_N = 1 << 25            # the partitioned tier's deployment size
QUERIES = 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _big_layout():
    spec = FilterSpec(dtype="u32", placement="single", n=BIG_N,
                      bits_per_key=14)
    layout, _ = _plan_layout(spec, _codec_for("u32"))
    assert layout.total_u32 > DEFAULT_VMEM_BUDGET_U32
    return layout


def _edge_layout():
    """The largest basic layout the resident budget holds."""
    lay = basic_layout(32, DEFAULT_VMEM_BUDGET_U32 * 32 // 14, 14.0,
                       delta=6)
    assert lay.total_u32 <= DEFAULT_VMEM_BUDGET_U32
    assert lay.total_u32 > DEFAULT_VMEM_BUDGET_U32 * 3 // 4
    return lay


def test_store_scan_streams_mixed_stack(one_chip):
    """A mixed-class run stack larger than the budget stays in HBM."""
    layouts = tuple(basic_layout(32, 4096 * 4 ** c, 14.0, delta=6)
                    for c in (0, 5))
    rowpad = max(lay.total_u32 for lay in layouts)
    R = len(layouts)
    assert R * rowpad > DEFAULT_VMEM_BUDGET_U32
    u32 = jnp.uint32
    _compile(store_scan_probe, layouts,
             _spec((R, rowpad), u32, one_chip),
             _spec((R,), u32, one_chip), _spec((R,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip), SCAN_TILE, False, False)


def test_range_probe_resident_at_budget_edge(one_chip):
    lay = _edge_layout()
    u32 = jnp.uint32
    _compile(range_probe_resident, lay,
             _spec((lay.total_u32,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip), 512, False)


def test_range_probe_stacked_resident_at_budget_edge(one_chip):
    """Two same-layout run rows that together fill the budget."""
    lay = basic_layout(32, DEFAULT_VMEM_BUDGET_U32 * 16 // 14, 14.0,
                       delta=6)
    assert 2 * lay.total_u32 <= DEFAULT_VMEM_BUDGET_U32
    u32 = jnp.uint32
    _compile(range_probe_stacked_resident, lay,
             _spec((2, lay.total_u32), u32, one_chip),
             _spec((QUERIES,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip), 512, False)


def test_point_probe_partitioned_at_2e25_keys(one_chip):
    lay = _big_layout()
    u32 = jnp.uint32
    _compile(point_probe_partitioned, lay,
             _spec((lay.total_u32,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip), 512, False)


def test_range_probe_partitioned_at_2e25_keys(one_chip):
    lay = _big_layout()
    u32 = jnp.uint32
    _compile(range_probe_partitioned, lay,
             _spec((lay.total_u32,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip),
             _spec((QUERIES,), u32, one_chip), 512, False)


def test_insert_resident_at_budget_edge(one_chip):
    lay = _edge_layout()
    assert BloomRF(lay, _warn=False)._probes_per_key >= 1
    _compile(insert_resident, lay,
             _spec((lay.total_u32,), jnp.uint32, one_chip),
             _spec((QUERIES,), jnp.uint32, one_chip), 512, False)
