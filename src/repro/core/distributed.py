"""Range-partitioned distributed DILI over a device mesh (shard_map).

The paper's equal-division trick (Eq. 1) *is* the router: partition boundaries
are chosen from key quantiles, and a query's shard comes from a searchsorted
over the (tiny, replicated) boundary array — one more "internal node" whose
children live on different chips.

Two lookup strategies:
  * ``gather``  (default, always correct): all_gather the query batch, search
    locally, return each result from its owner shard (`_owner_scatter`: an
    all_to_all plus a select).  Collective bytes: Q*8 gathered + Q*8
    returned per chip.
  * ``a2a``     (optimized, capacity-bounded): bucket queries by shard,
    all_to_all fixed-capacity buckets, search, all_to_all back.  Bytes:
    2*C*R*8 per chip with C = capacity per (src, dst) pair.  Falls back to
    `gather` results for overflowed queries (counted, asserted in tests).

Shard snapshots are padded to identical shapes so the whole index stacks into
leading-axis-sharded arrays -- republish never re-traces.

Online updates (DESIGN.md section 8): each shard owns a private tombstone
overlay absorbing the writes routed to its key range.  A merge folds ONE
shard's overlay through that shard's host DILI (Alg. 7/8), re-flattens only
that shard, and rewrites its rows of the stacked tables in place — no global
rebuild; the stack only re-pads when a shard outgrows the shared pow2 shape.
Reads between merges resolve the (globally sorted, because shard ranges are
disjoint) combined overlay on top of the sharded snapshot lookup.
"""

from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import watchdog
from .bu_tree import CostModel, DEFAULT_COST
from .dili import bulk_load
from .flat import FlatDILI, flatten
from . import search as S


@dataclass
class ShardedDILI:
    idx: dict              # stacked host arrays, leading dim = shard
    boundaries: np.ndarray  # [R+1] range boundaries (replicated)
    n_shards: int
    max_depth: int
    has_dense: bool = True  # any shard has dense (DILI-LO) leaves
    # online-update state (None when built with keep_host=False)
    flats: list | None = None      # per-shard FlatDILI (current epoch)
    dilis: list | None = None      # per-shard host DILI writers
    overlays: list | None = None   # per-shard TombstoneOverlay
    epoch: int = 0
    # device mirror of the combined overlay, keyed by dtype name;
    # invalidated by every write/merge
    _ov_cache: dict = field(default_factory=dict, repr=False)


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full((n,) + x.shape[1:], fill, dtype=x.dtype)
    out[: len(x)] = x
    return out


def _stack_flats(flats: list[FlatDILI]) -> dict:
    n_nodes = 1 << max(1, math.ceil(math.log2(max(f.n_nodes for f in flats))))
    n_slots = 1 << max(1, math.ceil(math.log2(max(f.n_slots for f in flats))))
    n_pairs = 1 << max(1, math.ceil(math.log2(max(max(f.n_pairs, 1)
                                                  for f in flats))))
    return dict(
        a=np.stack([_pad_to(f.a, n_nodes, 0.0) for f in flats]),
        b=np.stack([_pad_to(f.b, n_nodes, 0.0) for f in flats]),
        base=np.stack([_pad_to(f.base, n_nodes, 0) for f in flats]),
        fo=np.stack([_pad_to(f.fo, n_nodes, 1) for f in flats]),
        dense=np.stack([_pad_to(f.dense, n_nodes, 0) for f in flats]),
        tag=np.stack([_pad_to(f.tag, n_slots, 0) for f in flats]),
        key=np.stack([_pad_to(f.key, n_slots, 0.0) for f in flats]),
        # int64 payloads end-to-end (int32 wrapped payloads above 2^31)
        val=np.stack([_pad_to(f.val, n_slots, -1) for f in flats]),
        # key-sorted pair table per shard (range queries); +inf pads keep the
        # searchsorted window inside the populated prefix
        pair_key=np.stack([_pad_to(f.pair_key, n_pairs, np.inf)
                           for f in flats]),
        pair_val=np.stack([_pad_to(f.pair_val, n_pairs, -1) for f in flats]),
        root=np.array([f.root for f in flats], np.int32),
    )


def build_sharded(keys: np.ndarray, vals: np.ndarray | None, n_shards: int,
                  cm: CostModel = DEFAULT_COST, sample_stride: int = 1,
                  keep_host: bool = True, overlay_cap: int = 4096,
                  **kw) -> ShardedDILI:
    from ..online.overlay import TombstoneOverlay
    keys = np.asarray(keys, np.float64)
    n = len(keys)
    if vals is None:
        vals = np.arange(n, dtype=np.int64)
    # quantile partitioning: equal #keys per shard (balanced memory/work)
    cuts = [0] + [round(n * (i + 1) / n_shards) for i in range(n_shards)]
    flats: list[FlatDILI] = []
    dilis: list = []
    for r in range(n_shards):
        lo, hi = cuts[r], cuts[r + 1]
        d = bulk_load(keys[lo:hi], vals[lo:hi], cm=cm,
                      sample_stride=sample_stride, **kw)
        dilis.append(d)
        flats.append(flatten(d))
    boundaries = np.concatenate([[ -np.inf ],
                                 [keys[cuts[r]] for r in range(1, n_shards)],
                                 [np.inf]])
    stack = _stack_flats(flats)
    # depth-exact: the deepest shard's true height IS the trip count (padding
    # never deepens a tree, and off-range queries miss before going deeper)
    max_depth = max(f.max_depth for f in flats)
    sd = ShardedDILI(idx=stack, boundaries=boundaries, n_shards=n_shards,
                     max_depth=max_depth,
                     has_dense=any(bool(f.dense.any()) for f in flats))
    if keep_host:
        sd.flats = flats
        sd.dilis = dilis
        sd.overlays = [TombstoneOverlay.empty(overlay_cap)
                       for _ in range(n_shards)]
    return sd


def to_mesh(sd: ShardedDILI, mesh: Mesh, axis: str = "data",
            dtype=jnp.float64) -> dict:
    """Place each shard's arrays on its devices (leading dim sharded)."""
    sharding = NamedSharding(mesh, P(axis))
    out = {}
    for k, v in sd.idx.items():
        if k == "root":
            arr = jnp.asarray(v, jnp.int32)
        elif v.dtype == np.float64:
            arr = jnp.asarray(v, dtype)
        else:
            arr = jnp.asarray(v)
        out[k] = jax.device_put(arr, sharding)
    out["boundaries"] = jnp.asarray(sd.boundaries, dtype)  # replicated
    return out


def _local_search(local_idx: dict, q: jnp.ndarray, max_depth: int,
                  has_dense: bool = True):
    idx = {k: v[0] for k, v in local_idx.items() if k != "boundaries"}
    idx["root"] = local_idx["root"][0]
    idx["max_depth"] = max_depth
    idx["has_dense"] = has_dense       # static: skips the dense probe phases
    # depth-exact fixed-trip scan: shard_map's varying-axes check refuses
    # the early-exit while_loop, whose initial carry is not marked varying
    # over the mesh axis, so the early-exit variant stays off the mesh
    return S.search_batch(idx, q, max_depth=max_depth)


def _empty_overlay(dtype) -> dict:
    """Replicated no-op overlay: lets one shard_map trace serve both the
    plain and the overlay read path."""
    return dict(keys=jnp.full(1, np.inf, dtype),
                vals=jnp.zeros(1, jnp.int64),
                tomb=jnp.zeros(1, jnp.int8))


# trace cache for the collective entry points: the shard_map body is a
# fresh closure per call, so without this every batch would re-trace (and
# on CPU re-tracing dominates the dispatch by orders of magnitude).  Keys
# are the static closure parameters; Mesh hashes by device assignment +
# axis names, so equivalent meshes share entries.  jax.jit adds the
# per-shape executable cache on top.  LRU-bounded: a long-lived server
# with varying a2a batch sizes mints one entry per padded length, and
# each entry pins its compiled executables for the life of the process.
_TRACE_CACHE: "OrderedDict" = OrderedDict()
_TRACE_CACHE_MAX = 64


def _cached_collective(key, make):
    fn = _TRACE_CACHE.get(key)
    if fn is None:
        fn = _TRACE_CACHE[key] = jax.jit(make())
        if len(_TRACE_CACHE) > _TRACE_CACHE_MAX:
            _TRACE_CACHE.popitem(last=False)
    else:
        _TRACE_CACHE.move_to_end(key)
    return fn


def _collective_cache_sizes() -> dict:
    """Watchdog view of the collective trace cache: entry count plus total
    traced executables across entries.  A per-batch growth here is exactly
    the PR-4 bug class (fresh shard_map closure per call => re-trace)."""
    total = 0
    for fn in _TRACE_CACHE.values():
        try:
            total += fn._cache_size()
        except Exception:
            pass
    return {"distributed.collective_cache_entries": len(_TRACE_CACHE),
            "distributed.collective_executables": total}


watchdog.register_jit_provider("distributed.collectives",
                               _collective_cache_sizes)


def _owner_scatter(own: jnp.ndarray, xs: tuple, axis: str,
                   n_shards: int) -> tuple[list, jnp.ndarray]:
    """Each device's slice of every `x` in `xs` ([Q_total, ...] on every
    shard), taken from the one shard whose `own` mask is set for that
    element (zero where none is), plus the slice of that mask: whether
    any shard owned the element.

    This is the reduce-scatter of the masked values, written as an
    all_to_all plus a select: XLA:TPU has no 64-bit reduce-scatter (and
    cannot split an f64 into 32-bit words either), while all_to_all moves
    any dtype.  The bytes moved are the reduce-scatter's plus one int32
    mask, and the select is bit-exact."""
    def a2a(v):                  # [Q_total, ...] -> [R, Q_total / R, ...]
        v = jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        return v.reshape((n_shards, -1) + v.shape[1:])
    owns = a2a(own.astype(jnp.int32)) > 0
    outs = []
    for x in xs:
        xr = a2a(x)
        out = jnp.zeros(xr.shape[1:], x.dtype)
        for r in range(n_shards):
            out = jnp.where(owns[r], xr[r], out)
        outs.append(out)
    return outs, owns.any(axis=0)


def sharded_lookup(mesh: Mesh, sd_arrays: dict, queries: jnp.ndarray,
                   max_depth: int, axis: str = "data",
                   strategy: str = "gather", overlay: dict | None = None,
                   has_dense: bool = True):
    """Batched lookup across the mesh.  `queries` sharded over `axis`.

    `overlay` (a replicated combined-overlay dict) is resolved INSIDE the
    shard_map body — snapshot traversal + overlay searchsorted are one fused
    device dispatch, with no host round-trip between them.  Each query's
    overlay state is applied by the one shard that owns its key range."""
    n_shards = mesh.shape[axis]
    bounds = sd_arrays["boundaries"]
    ov = overlay if overlay is not None else _empty_overlay(bounds.dtype)

    in_specs = ({k: P(axis) for k in sd_arrays if k != "boundaries"}
                | {"boundaries": P()})
    ov_specs = {k: P() for k in ov}
    cache_key = (mesh, axis, strategy, max_depth, has_dense,
                 tuple(sorted(sd_arrays)), tuple(sorted(ov)),
                 queries.shape[0] if strategy == "a2a" else None)

    if strategy == "gather":
        def body(local, bnd, ovr, q):
            r = jax.lax.axis_index(axis)
            q_all = jax.lax.all_gather(q, axis, tiled=True)       # [Q_total]
            v, f = _local_search(local, q_all, max_depth, has_dense)
            v, f = S.resolve_overlay(ovr, q_all, v, f)
            # mask to own range: boundaries[r] <= q < boundaries[r+1]
            own = (q_all >= bnd[r]) & (q_all < bnd[r + 1])
            # each device's slice, from the shard owning each query's key
            (v,), f = _owner_scatter(own & f, (v,), axis, n_shards)
            return v, f

        fn = _cached_collective(cache_key, lambda: jax.shard_map(
            body, mesh=mesh,
            in_specs=(in_specs, P(), ov_specs, P(axis)),
            out_specs=(P(axis), P(axis))))
        return fn(sd_arrays, bounds, ov, queries)

    elif strategy == "a2a":
        qn = queries.shape[0] // n_shards          # per-device query count
        cap = int(2 * math.ceil(qn / n_shards))    # capacity slack 2x

        def body(local, bnd, ovr, q):
            r = jax.lax.axis_index(axis)
            dest = jnp.clip(jnp.searchsorted(bnd, q, side="right") - 1,
                            0, n_shards - 1)                     # [qn]
            # bucket into [R, cap] with overflow detection
            order = jnp.argsort(dest)
            q_sorted, d_sorted = q[order], dest[order]
            # position within bucket
            onehot = jax.nn.one_hot(d_sorted, n_shards, dtype=jnp.int32)
            within = jnp.cumsum(onehot, axis=0)[jnp.arange(qn), d_sorted] - 1
            ok = within < cap
            buckets = jnp.full((n_shards, cap), jnp.inf, q.dtype)
            buckets = buckets.at[d_sorted, jnp.clip(within, 0, cap - 1)].set(
                jnp.where(ok, q_sorted, jnp.inf))
            recv = jax.lax.all_to_all(buckets, axis, split_axis=0,
                                      concat_axis=0, tiled=True)  # [R*cap]
            v, f = _local_search(local, recv.reshape(-1), max_depth,
                                 has_dense)
            v, f = S.resolve_overlay(ovr, recv.reshape(-1), v, f)
            v = v.reshape(n_shards, cap)
            f = f.reshape(n_shards, cap)
            vb = jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0,
                                    tiled=True).reshape(n_shards, cap)
            fb = jax.lax.all_to_all(f, axis, split_axis=0, concat_axis=0,
                                    tiled=True).reshape(n_shards, cap)
            # unbucket: gather each sorted query's result, unsort
            vs = vb[d_sorted, jnp.clip(within, 0, cap - 1)]
            fs = fb[d_sorted, jnp.clip(within, 0, cap - 1)] & ok
            inv = jnp.argsort(order)
            return vs[inv], fs[inv], jnp.sum(~ok).astype(jnp.int32)[None]

        fn = _cached_collective(cache_key, lambda: jax.shard_map(
            body, mesh=mesh,
            in_specs=(in_specs, P(), ov_specs, P(axis)),
            out_specs=(P(axis), P(axis), P(axis))))
        return fn(sd_arrays, bounds, ov, queries)
    raise ValueError(strategy)


def sharded_range_query(mesh: Mesh, sd_arrays: dict, lo: jnp.ndarray,
                        hi: jnp.ndarray, max_hits: int = 128,
                        axis: str = "data"):
    """Range queries across the mesh: for each (lo, hi) return the first
    `max_hits` pairs in [lo, hi) ascending plus the count (saturating).

    Each shard bisects ITS key-sorted pair table over the window clipped to
    its own key range — O(log n_shard + max_hits) per query per shard — then
    writes its run into the global answer at the offset given by the
    exclusive prefix of per-shard counts (shard ranges are disjoint and
    ordered, so shard-order concatenation IS key order).  `_owner_scatter`
    assembles and returns each device's query slice.
    """
    n_shards = mesh.shape[axis]
    bounds = sd_arrays["boundaries"]
    in_specs = ({k: P(axis) for k in sd_arrays if k != "boundaries"}
                | {"boundaries": P()})

    def body(local, bnd, lo, hi):
        r = jax.lax.axis_index(axis)
        lo_all = jax.lax.all_gather(lo, axis, tiled=True)        # [Q]
        hi_all = jax.lax.all_gather(hi, axis, tiled=True)
        pk = local["pair_key"][0]
        pv = local["pair_val"][0]
        # clip the window to this shard's key range
        slo = jnp.maximum(lo_all, bnd[r])
        shi = jnp.maximum(jnp.minimum(hi_all, bnd[r + 1]), slo)
        start = jnp.searchsorted(pk, slo, side="left")
        cnt = (jnp.searchsorted(pk, shi, side="left")
               - start).astype(jnp.int32)                        # [Q]
        # exclusive prefix of counts over earlier shards = this run's offset
        cnt_all = jax.lax.all_gather(cnt, axis)                  # [R, Q]
        before = jnp.sum(
            jnp.where(jnp.arange(n_shards)[:, None] < r, cnt_all, 0), axis=0)
        posn = jnp.arange(max_hits)[None, :]                     # [1, H]
        rel = posn - before[:, None]                             # [Q, H]
        mine = (rel >= 0) & (rel < cnt[:, None])
        g = jnp.clip(start[:, None] + rel, 0, pk.shape[0] - 1)
        # exactly one shard owns each (query, position)
        (ks, vs), _ = _owner_scatter(mine, (pk[g], pv[g]), axis, n_shards)
        total = jax.lax.psum_scatter(cnt, axis, scatter_dimension=0,
                                     tiled=True)                 # [Q/R]
        filled = posn < jnp.minimum(total, max_hits)[:, None]
        ks = jnp.where(filled, ks, jnp.inf)
        vs = jnp.where(filled, vs, -1)
        return ks, vs, jnp.minimum(total, max_hits).astype(jnp.int32)

    fn = _cached_collective(
        (mesh, axis, "range", max_hits, tuple(sorted(sd_arrays))),
        lambda: jax.shard_map(
            body, mesh=mesh,
            in_specs=(in_specs, P(), P(axis), P(axis)),
            out_specs=(P(axis, None), P(axis, None), P(axis))))
    return fn(sd_arrays, bounds, lo, hi)


# ---------------------------------------------------------------------------
# Online updates: per-shard overlays, single-shard merge, fused read path
# ---------------------------------------------------------------------------


def shard_of(sd: ShardedDILI, keys: np.ndarray) -> np.ndarray:
    """Route keys to shards: the boundary array is the root 'internal node'."""
    return np.clip(np.searchsorted(sd.boundaries, keys, side="right") - 1,
                   0, sd.n_shards - 1)


def _require_host(sd: ShardedDILI) -> None:
    if sd.overlays is None:
        raise ValueError("build_sharded(..., keep_host=True) required for "
                         "online updates")


def sharded_upsert(sd: ShardedDILI, keys, vals) -> None:
    _require_host(sd)
    keys = np.atleast_1d(np.asarray(keys, np.float64))
    vals = np.atleast_1d(np.asarray(vals, np.int64))
    dest = shard_of(sd, keys)
    for r in np.unique(dest):
        m = dest == r
        sd.overlays[r] = sd.overlays[r].upsert_batch(keys[m], vals[m])
    sd._ov_cache.clear()


def sharded_delete(sd: ShardedDILI, keys) -> None:
    _require_host(sd)
    keys = np.atleast_1d(np.asarray(keys, np.float64))
    dest = shard_of(sd, keys)
    for r in np.unique(dest):
        m = dest == r
        sd.overlays[r] = sd.overlays[r].delete_batch(keys[m])
    sd._ov_cache.clear()


def sharded_merge(sd: ShardedDILI, max_fill: float = 0.0,
                  fold_fn=None, flatten_fn=None) -> list[int]:
    """Fold each shard whose overlay full_fraction exceeds `max_fill` through
    its host DILI (Alg. 7/8), re-flatten ONLY those shards, and rewrite their
    rows of the stacked tables in place.  The stack is re-padded (bigger pow2)
    only when a merged shard outgrows the shared shape.  Returns merged shard
    ids; bumps `sd.epoch` when any merged.

    `fold_fn(r, dili, overlay)` / `flatten_fn(r, dili) -> FlatDILI` override
    the per-shard fold and flatten — the maintenance hooks the sharded
    engine uses to route through accounting/retrains and the incremental
    flattener (defaults: plain `fold_overlay` / full `flatten`).

    NOTE: only the HOST stack (`sd.idx`) is rewritten, and the merged
    overlays are cleared — device copies from a prior `to_mesh()` no longer
    see the folded writes.  Callers must republish (`to_mesh(sd, mesh)`)
    before serving lookups whenever this returns a non-empty list."""
    from ..online.overlay import TombstoneOverlay, fold_overlay
    _require_host(sd)
    if fold_fn is None:
        fold_fn = lambda r, d, ov: fold_overlay(d, ov)   # noqa: E731
    if flatten_fn is None:
        # drain the dirty-id set a full flatten supersedes (it would
        # otherwise grow for the lifetime of a maintenance-less shard)
        def flatten_fn(r, d):
            f = flatten(d)
            d.take_dirty()
            return f
    merged: list[int] = []
    for r, ov in enumerate(sd.overlays):
        if ov.count == 0 or ov.full_fraction < max_fill:
            continue
        fold_fn(r, sd.dilis[r], ov)
        sd.flats[r] = flatten_fn(r, sd.dilis[r])
        sd.overlays[r] = TombstoneOverlay.empty(ov.cap)
        merged.append(r)
    if not merged:
        return merged
    sd._ov_cache.clear()
    n_nodes = sd.idx["a"].shape[1]
    n_slots = sd.idx["tag"].shape[1]
    n_pairs = sd.idx["pair_key"].shape[1]
    if any(sd.flats[r].n_nodes > n_nodes or sd.flats[r].n_slots > n_slots
           or sd.flats[r].n_pairs > n_pairs for r in merged):
        sd.idx = _stack_flats(sd.flats)      # grow: re-pad every shard
    else:
        for r in merged:                     # steady state: row rewrite only
            f = sd.flats[r]
            sd.idx["a"][r] = _pad_to(f.a, n_nodes, 0.0)
            sd.idx["b"][r] = _pad_to(f.b, n_nodes, 0.0)
            sd.idx["base"][r] = _pad_to(f.base, n_nodes, 0)
            sd.idx["fo"][r] = _pad_to(f.fo, n_nodes, 1)
            sd.idx["dense"][r] = _pad_to(f.dense, n_nodes, 0)
            sd.idx["tag"][r] = _pad_to(f.tag, n_slots, 0)
            sd.idx["key"][r] = _pad_to(f.key, n_slots, 0.0)
            sd.idx["val"][r] = _pad_to(f.val, n_slots, -1)
            sd.idx["pair_key"][r] = _pad_to(f.pair_key, n_pairs, np.inf)
            sd.idx["pair_val"][r] = _pad_to(f.pair_val, n_pairs, -1)
            sd.idx["root"][r] = f.root
    sd.max_depth = max(f.max_depth for f in sd.flats)
    sd.has_dense = any(bool(f.dense.any()) for f in sd.flats)
    sd.epoch += 1
    return merged


def combined_overlay_arrays(sd: ShardedDILI, dtype=jnp.float64) -> dict:
    """One globally sorted overlay view: shard key ranges are disjoint, so
    concatenating per-shard populated prefixes in shard order IS sorted.
    Cached per dtype; writes and merges invalidate."""
    _require_host(sd)
    ckey = np.dtype(dtype).name
    hit = sd._ov_cache.get(ckey)
    if hit is not None:
        return hit
    parts = [ov.entries() for ov in sd.overlays]
    ks = np.concatenate([p[0] for p in parts])
    vs = np.concatenate([p[1] for p in parts])
    tb = np.concatenate([p[2] for p in parts])
    # pad to (at least) the summed per-shard capacities, not the populated
    # count: caps start at the configured overlay_cap and only grow by
    # doubling, so the replicated mirror keeps ONE shape from idle through
    # write-heavy periods and the fused collective re-traces only when a
    # shard's overlay doubles — the exact policy of the local engine's
    # cap-sized mirror (overlay_device_arrays).  Pow2-of-count padding
    # instead re-traced at every pow2 crossing.  The mirror is rebuilt only
    # when the _ov_cache was invalidated by a write or merge, never on the
    # read path, so the cap-sized concat is off the serving hot loop.
    floor = sum(ov.cap for ov in sd.overlays)
    cap = 1 << max(1, math.ceil(math.log2(max(len(ks), floor, 1))))
    out = dict(keys=jnp.asarray(_pad_to(ks, cap, np.inf), dtype),
               vals=jnp.asarray(_pad_to(vs, cap, 0), jnp.int64),
               tomb=jnp.asarray(_pad_to(tb, cap, 0), jnp.int8))
    sd._ov_cache[ckey] = out
    return out


def sharded_lookup_with_overlay(mesh: Mesh, sd_arrays: dict,
                                sd: ShardedDILI, queries: jnp.ndarray,
                                max_depth: int, axis: str = "data",
                                strategy: str = "gather"):
    """Sharded snapshot lookup with the (replicated) combined overlay
    resolved inside the shard_map body — ONE fused device dispatch per query
    batch, no extra host round-trip for the overlay pass."""
    ova = combined_overlay_arrays(sd, sd_arrays["boundaries"].dtype)
    return sharded_lookup(mesh, sd_arrays, queries, max_depth, axis=axis,
                          strategy=strategy, overlay=ova,
                          has_dense=sd.has_dense)
