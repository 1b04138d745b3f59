"""Compile the main path's kernels for a TPU v5e that is described, not
attached, at the widths ``chip_smoke.py`` serves on the chip.

What the chip's compiler refuses here (a misaligned block, a gather
Mosaic cannot lower, a program over the device's memory) costs no chip
time. Nothing runs: these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the driver's test
workers import every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_oracle_search_tpu.data import synth_city_graph
from distributed_oracle_search_tpu.models.cpd import pick_build_kernel
from distributed_oracle_search_tpu.ops.device_graph import DeviceGraph
from distributed_oracle_search_tpu.ops.grid_sweep import build_fm_columns_sweep
from distributed_oracle_search_tpu.ops.pallas_walk import (
    TPU_REFUSAL, _pallas_walk,
)
from distributed_oracle_search_tpu.ops.table_search import table_search_batch

#: chip_smoke.py's deployment: the 320x320 city, worker 0 of 8 (mod)
SIDE, ROWS = 320, 12800
#: the gateway's padded batch (chip_smoke.py serves --max-batch 512)
QPAD = 512
#: worker.build rows per kernel call (chip_smoke.py --chunk)
BUILD_CHUNK = 1024
#: v5e HBM per chip
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def city():
    return synth_city_graph(SIDE, SIDE, seed=0)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_graph(sharding, g):
    n, k = g.n, g.max_out_degree
    return DeviceGraph(_spec(sharding, (n, k), jnp.int32),
                       _spec(sharding, (n, k), jnp.int32),
                       _spec(sharding, (len(g.w) + 1,), jnp.int32))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_xla_walk_compiles_at_shard_width(one_chip, city):
    """The walk the gateway serves: worker 0's [12800, 102400] int8
    shard at the padded batch, under a diff (query-time weights)."""
    dg = _device_graph(one_chip, city)
    q = [_spec(one_chip, (QPAD,), jnp.int32) for _ in range(3)]
    compiled = jax.jit(table_search_batch).lower(
        dg, _spec(one_chip, (ROWS, city.n), jnp.int8), *q, dg.w_pad,
        _spec(one_chip, (QPAD,), jnp.bool_)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_build_kernel_compiles_for_one_block(one_chip, city):
    """The build kernel ``pick_build_kernel`` picks for the city, at
    one kernel call of rows."""
    kind, grid = pick_build_kernel(city)
    assert kind == "sweep"
    compiled = jax.jit(
        lambda dg, targets: build_fm_columns_sweep(dg, grid, targets)
    ).lower(_device_graph(one_chip, city),
            _spec(one_chip, (BUILD_CHUNK,), jnp.int32)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.xfail(strict=True, raises=AssertionError, reason=TPU_REFUSAL)
def test_pallas_walk_compiles(one_chip):
    """The canned 432-node city, one bucket of 512 queries: small
    enough to pass the VMEM-fit check, so only the compiler decides."""
    g = synth_city_graph(24, 18, seed=0)
    dg = _device_graph(one_chip, g)
    q = [_spec(one_chip, (QPAD,), jnp.int32) for _ in range(3)]
    _pallas_walk.lower(
        dg, _spec(one_chip, (8, g.n), jnp.int8), *q, dg.w_pad,
        _spec(one_chip, (QPAD,), jnp.bool_), k_moves=-1, max_steps=0,
        unroll=8, n_buckets=1, interpret=False, packed4=False).compile()


def test_mesh_walk_compiles_at_campaign_width(topo):
    """The campaign's walk on the 4-chip host (``city384-tpu4``): the
    384x384 city's table, 36,864 target rows per chip, at the routed
    width of a part of 2^16 uniform pairs; compiled under its stable
    program name, with no copy of the table."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_oracle_search_tpu.parallel.mesh import (
        DATA_AXIS, WORKER_AXIS,
    )
    from distributed_oracle_search_tpu.parallel.sharded import _query_fn

    mesh = Mesh(np.array(topo.devices).reshape(1, 4),
                (DATA_AXIS, WORKER_AXIS))
    g = synth_city_graph(384, 384, seed=0)
    rows = -(-g.n // 4)
    rep = NamedSharding(mesh, P())
    q3 = NamedSharding(mesh, P(DATA_AXIS, WORKER_AXIS, None))
    dg = _device_graph(rep, g)
    q = [_spec(q3, (1, 4, 32768), jnp.int32) for _ in range(3)]
    lowered = _query_fn(mesh, 0, -1, "xla").lower(
        dg, _spec(NamedSharding(mesh, P(WORKER_AXIS, None, None)),
                  (4, rows, g.n), jnp.int8),
        *q, _spec(q3, (1, 4, 32768), jnp.bool_), dg.w_pad)
    assert "module @jit_mesh_walk" in lowered.as_text()
    compiled = lowered.compile()
    assert _device_bytes(compiled) < HBM_BYTES
    # the walk reads the resident table in place: its temp is the
    # lanes' state, far below one table copy
    assert compiled.memory_analysis().temp_size_in_bytes < rows * g.n // 100
