"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so the main device paths can be
compiled for a `v5e:2x2` topology on a machine with no chip.  That catches
what interpret mode and the CPU backend cannot: Mosaic refusing a kernel,
XLA:TPU refusing a 64-bit collective, a program that does not fit.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the test workers all import
this file.  The persistent compilation cache is off around these compiles
(an entry compiled for a described chip cannot be read back without one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import search as S
from repro.core.distributed import sharded_lookup, sharded_range_query
from repro.kernels.dili_search import dili_search_pallas
from repro.kernels.ops import VMEM_BUDGET_BYTES

#: the local engine's tables at 2^22 slots (a ~4M-key index)
N_NODES, N_SLOTS, N_PAIRS = 1 << 16, 1 << 22, 1 << 22
N_QUERIES = 4096
MAX_DEPTH = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # noqa: BLE001 — any failure = no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture(autouse=False)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _snapshot_shapes(sharding, lead=()):
    """The local engine's f64 snapshot tables as shapes (`lead` prepends
    the shard axis of the sharded engine's stacked tables)."""
    f64, i64, i32, i8 = jnp.float64, jnp.int64, jnp.int32, jnp.int8
    cols = dict(a=(N_NODES, f64), b=(N_NODES, f64), base=(N_NODES, i32),
                fo=(N_NODES, i32), dense=(N_NODES, i8), tag=(N_SLOTS, i8),
                key=(N_SLOTS, f64), val=(N_SLOTS, i64),
                pair_key=(N_PAIRS, f64), pair_val=(N_PAIRS, i64))
    return {k: _shape(lead + (n,), dt, sharding)
            for k, (n, dt) in cols.items()}


# -- the Pallas kernel ---------------------------------------------------------


@pytest.mark.parametrize("n_nodes,n_slots", [
    (1 << 10, 1 << 14),
    (1 << 14, 1 << 19),
    # near the default VMEM budget: 5 node + 3 slot words of 4 bytes
    (1 << 16, (VMEM_BUDGET_BYTES - 20 * (1 << 16)) // 12),
])
def test_kernel_compiles(one_chip, no_persistent_cache, n_nodes, n_slots):
    f32, i32 = jnp.float32, jnp.int32
    nodes = [_shape((n_nodes,), dt, one_chip) for dt in (f32, f32, i32, i32,
                                                        i32)]
    slots = [_shape((n_slots,), dt, one_chip) for dt in (i32, f32, i32)]
    fn = jax.jit(functools.partial(dili_search_pallas, max_depth=MAX_DEPTH,
                                   interpret=False))
    compiled = fn.lower(*nodes, *slots, _shape((1,), i32, one_chip),
                        _shape((65536,), f32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the local engine's fused f64 lookup and range -----------------------------


def test_fused_f64_lookup_compiles(one_chip, no_persistent_cache):
    idx = _snapshot_shapes(one_chip)
    idx["node_pack"] = _shape((N_NODES, 4), jnp.float64, one_chip)
    idx["slot_pack"] = _shape((N_SLOTS, 2), jnp.float64, one_chip)
    idx["root"] = _shape((), jnp.int32, one_chip)
    idx["max_depth"] = _shape((), jnp.int32, one_chip)
    ov = dict(keys=_shape((8192,), jnp.float64, one_chip),
              vals=_shape((8192,), jnp.int64, one_chip),
              tomb=_shape((8192,), jnp.int8, one_chip))
    q = _shape((N_QUERIES,), jnp.float64, one_chip)
    fn = jax.jit(lambda idx, ov, q: S.search_with_overlay(
        dict(idx, has_dense=False), ov, q, max_depth=MAX_DEPTH))
    fn.lower(idx, ov, q).compile()


def test_f64_range_compiles(one_chip, no_persistent_cache):
    idx = _snapshot_shapes(one_chip)
    lo = _shape((N_QUERIES,), jnp.float64, one_chip)
    fn = jax.jit(lambda idx, lo, hi: S.range_query_batch(idx, lo, hi,
                                                         max_hits=128))
    fn.lower(idx, lo, lo).compile()


# -- the sharded engine's collectives on four chips ----------------------------


def _sharded_shapes(mesh):
    arrs = _snapshot_shapes(NamedSharding(mesh, P("data")), lead=(4,))
    arrs["root"] = _shape((4,), jnp.int32, NamedSharding(mesh, P("data")))
    arrs["boundaries"] = _shape((5,), jnp.float64, NamedSharding(mesh, P()))
    return arrs


@pytest.mark.parametrize("strategy", ["gather", "a2a"])
def test_sharded_lookup_compiles(mesh, no_persistent_cache, strategy):
    q = _shape((N_QUERIES,), jnp.float64, NamedSharding(mesh, P("data")))
    fn = jax.jit(lambda arrs, q: sharded_lookup(
        mesh, arrs, q, MAX_DEPTH, strategy=strategy, has_dense=False))
    fn.lower(_sharded_shapes(mesh), q).compile()


def test_sharded_range_compiles(mesh, no_persistent_cache):
    lo = _shape((N_QUERIES,), jnp.float64, NamedSharding(mesh, P("data")))
    fn = jax.jit(lambda arrs, lo, hi: sharded_range_query(
        mesh, arrs, lo, hi, max_hits=128))
    fn.lower(_sharded_shapes(mesh), lo, lo).compile()
