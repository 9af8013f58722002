"""Pallas TPU kernel: batched DILI point lookup (the paper's hot loop).

TPU adaptation of Algorithm 6 (DESIGN.md section 2).  The node table (a, b,
base, fo, dense) and the slot table (tag, key, val) stay VMEM-resident for
the whole call; tables above the VMEM budget take the XLA path instead
(ops.py dispatches).  Queries are tiled into SMEM blocks of BLOCK_Q.

Mosaic does not lower a per-lane vector gather from an arbitrary VMEM
table, so the traversal is a per-query scalar walk: each query is read
from SMEM, and each level of Algorithm 6 reads the node's and the slot's
fields with a dynamic-row load of the lane-dense `(rows, 128)` table and a
masked lane reduction to a scalar.  The walk stops at the first hit or
miss, or after `max_depth` levels.

A dense (DILI-LO) leaf, which f32 placement produces wherever it cannot
resolve slots, keeps its pairs packed and sorted.  Its slot for q is the
count of its keys below q (the first key >= q of Algorithm 1), taken over
the aligned `(8, 128)` tiles that hold the leaf: independent loads and
compares, then one reduction, where a bisection would chain log2(fo)
reads that each wait on the one before.  A model level costs eight reads
per query; a dense leaf six plus the count, about fo / 1024 + 1 tile
loads and a reduction.  The walk is still one query at a time and bound
by the latency of its dependent reads, far below the bandwidth roofline.

Depth overflow sets a `needs_fallback` flag; the wrapper re-checks those
lanes with the pure-XLA path.

Mosaic rejects 64-bit scalars and i1 outputs, so every index, flag and
constant in the kernel is int32 explicitly (the library runs with
`jax_enable_x64`, where Python ints would become int64), and the three
outputs are int32, cast to bool outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TAG_EMPTY, TAG_PAIR, TAG_CHILD = 0, 1, 2

BLOCK_Q = 2048   # queries per grid step (one SMEM block)
LANES = 128
_SUBLANES = 8
_CHUNK = _SUBLANES * LANES   # slots in one aligned (8, 128) tile
#: VMEM headroom above the tables for the compiler's own buffers
_VMEM_SLACK = 16 * 1024 * 1024


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode follows the backend: compiled on a TPU, interpreted
    elsewhere.  Asking for the interpreter on a TPU is refused, so a
    caller can never run the slow path there by accident."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: the kernel runs "
                         "compiled there (leave interpret unset)")
    return bool(interpret)


def _lane_dense(x: jnp.ndarray) -> jnp.ndarray:
    """1-D table -> zero-padded (rows, 128) with rows a multiple of 8."""
    n = x.shape[0]
    padded = -(-n // _CHUNK) * _CHUNK
    return jnp.pad(x, (0, padded - n)).reshape(padded // LANES, LANES)


def _kernel(root_ref, q_ref, a_ref, b_ref, base_ref, fo_ref, dense_ref,
            tag_ref, key_ref, val_ref, out_ref, found_ref, fb_ref, *,
            max_depth: int, block_q: int):
    i32 = jnp.int32
    lane = jax.lax.broadcasted_iota(i32, (1, LANES), 1)
    sub = jax.lax.broadcasted_iota(i32, (_SUBLANES, LANES), 0)
    lanes = jax.lax.broadcasted_iota(i32, (_SUBLANES, LANES), 1)

    def read(ref, i):
        # element i of a lane-dense table: load its row, keep its lane
        row = ref[pl.ds(jax.lax.div(i, i32(LANES)), 1), :]
        low = jnp.array(-jnp.inf if row.dtype == jnp.float32
                        else jnp.iinfo(jnp.int32).min, row.dtype)
        return jnp.max(jnp.where(lane == jax.lax.rem(i, i32(LANES)), row,
                                 low))

    def walk(j, carry):
        q = q_ref[j]

        def dense_slot(n):
            # DILI-LO leaf (Algorithm 1): its pairs are packed and sorted in
            # [base, base + fo), so the first key >= q sits at the count of
            # keys < q there, taken over the (8, 128) tiles that hold them
            base, fo = read(base_ref, n), read(fo_ref, n)
            end = base + fo
            c0 = jax.lax.div(base, i32(_CHUNK))
            n_chunks = jax.lax.div(end + i32(_CHUNK - 1), i32(_CHUNK)) - c0

            def count(c, acc):
                row = pl.multiple_of((c0 + c) * i32(_SUBLANES), _SUBLANES)
                slot = (row + sub) * i32(LANES) + lanes
                below = ((slot >= base) & (slot < end)
                         & (key_ref[pl.ds(row, _SUBLANES), :] < q))
                return acc + below.astype(jnp.float32)

            # counted in f32, which is exact: a leaf fits VMEM, far below
            # 2^24 slots (an int32 sum to a scalar lowers through int64
            # under x64, which Mosaic refuses)
            acc = jax.lax.fori_loop(i32(0), n_chunks, count,
                                    jnp.zeros((_SUBLANES, LANES),
                                              jnp.float32))
            lo = jnp.sum(acc).astype(i32)
            return base + jnp.minimum(lo, jnp.maximum(fo - 1, i32(0)))

        def model_slot(n):
            pos = jnp.floor(read(a_ref, n) + read(b_ref, n) * q).astype(i32)
            pos = jnp.minimum(jnp.maximum(pos, i32(0)), read(fo_ref, n) - 1)
            return read(base_ref, n) + pos

        def more(st):
            depth, _, done, _, _ = st
            return (depth < max_depth) & (done == 0)

        def level(st):
            depth, n, _, out, found = st
            is_dense = read(dense_ref, n) > 0
            s = jax.lax.cond(is_dense, dense_slot, model_slot, n)
            t = read(tag_ref, s)
            sv = read(val_ref, s)
            hit = (t == TAG_PAIR) & (read(key_ref, s) == q)
            child = (t == TAG_CHILD) & ~is_dense
            return (depth + 1,
                    jnp.where(child, sv, n),
                    jnp.where(child, i32(0), i32(1)),
                    jnp.where(hit, sv, out),
                    jnp.where(hit, i32(1), found))

        zero = i32(0)
        _, _, done, out, found = jax.lax.while_loop(
            more, level, (zero, root_ref[0], zero, zero - 1, zero))
        out_ref[j] = out
        found_ref[j] = found
        fb_ref[j] = jnp.where(done == 0, i32(1), zero)   # ran out of depth
        return carry

    jax.lax.fori_loop(i32(0), i32(block_q), walk, i32(0))


def dili_search_pallas(a, b, base, fo, dense, tag, key, val, root, queries,
                       max_depth: int, interpret: bool | None = None,
                       block_q: int = BLOCK_Q):
    """Returns (vals, found, needs_fallback) for a batch of f32 queries
    whose length is a multiple of `block_q`.  `interpret=None` follows the
    backend (see `resolve_interpret`)."""
    return _dili_search_pallas(a, b, base, fo, dense, tag, key, val, root,
                               queries, max_depth=max_depth,
                               interpret=resolve_interpret(interpret),
                               block_q=block_q)


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "interpret", "block_q"))
def _dili_search_pallas(a, b, base, fo, dense, tag, key, val, root, queries,
                        max_depth: int, interpret: bool, block_q: int):
    nq = queries.shape[0]
    assert nq % block_q == 0, f"pad queries to a multiple of {block_q}"
    tables = [_lane_dense(t) for t in (a, b, base, fo, dense, tag, key, val)]
    table_bytes = sum(t.size * t.dtype.itemsize for t in tables)
    # query/result blocks walk the grid; the tables are one whole-array VMEM
    # block each (explicit int32 block indices: the default ones are int64
    # under x64, which Mosaic refuses)
    qspec = pl.BlockSpec((block_q,), lambda i, root: (i,),
                         memory_space=pltpu.SMEM)
    tspec = pl.BlockSpec(index_map=lambda i, root: (jnp.int32(0),) * 2,
                         memory_space=pltpu.VMEM)
    out, found, fb = pl.pallas_call(
        functools.partial(_kernel, max_depth=max_depth, block_q=block_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nq // block_q,),
            in_specs=[qspec] + [tspec] * len(tables),
            out_specs=[qspec] * 3),
        out_shape=[jax.ShapeDtypeStruct((nq,), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(table_bytes + _VMEM_SLACK)),
        interpret=interpret,
        name="dili_search",
    )(jnp.asarray(root, jnp.int32).reshape(1), queries, *tables)
    return out, found > 0, fb > 0
