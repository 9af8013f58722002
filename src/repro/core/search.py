"""Batched device-side DILI search (pure JAX reference path).

Level-synchronous traversal: a batch of Q queries advances together through
the unified node/slot tables (flat.py).  Each round costs one FMA + floor +
clamp and a handful of 1-D column gathers per query — the TPU adaptation of
Algorithm 6's pointer chase.  Every table is a 1-D column, which XLA:TPU
lays out lane-dense; a 2-D row mirror with a narrow minor dimension would be
padded to 128 lanes per row and re-laid out on every call.  Dense (DILI-LO)
leaves exit the loop and run the paper's exponential search (Algorithm 1) as
a bounded vectorized probe sequence.

Cost model (DESIGN.md section 9): traversal work is *depth-exact* — the trip
count is the snapshot's true `max_depth` (derived via `resolve_max_depth`,
never hard-coded), and the `early_exit` variant stops the whole batch as soon
as every lane is done, so a batch whose lanes all bottom out at height 3 pays
3 rounds of gathers, not a fixed worst-case scan.  Range queries bisect the
key-sorted pair table built at flatten() time — O(log n + max_hits) per
query — instead of mask-scanning the global slot table.

All functions take the snapshot as a dict of jnp arrays (see `device_arrays`)
so they can be jitted/donated and fed to shard_map without re-tracing on every
publish (shapes are padded to powers of two).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import watchdog
from .flat import TAG_CHILD, TAG_EMPTY, TAG_PAIR, DeltaOverlay, FlatDILI

def predict_slot(a, b, q, fo):
    """floor(a + b*q) clipped to [0, fo).

    CRITICAL: XLA fuses `a + b*q` into an FMA whose single rounding differs
    from numpy's mul-then-add at exact-integer boundaries (e.g. 2.0 vs
    1.999...), sending a query to the wrong slot.  Construction places pairs
    with numpy semantics, so the search MUST evaluate mul-then-add with two
    IEEE roundings — the optimization_barrier blocks the FMA fusion.
    (Found the hard way; regression test: tests/test_search.py::test_fma_consistency.)
    """
    bq = jax.lax.optimization_barrier(b * q)
    return jnp.clip(jnp.floor(a + bq).astype(jnp.int32), 0, fo - 1)


def _pad_pow2(x: np.ndarray, fill) -> np.ndarray:
    n = len(x)
    m = 1 << max(int(math.ceil(math.log2(max(n, 1)))), 0)
    if m == n:
        return x
    out = np.full(m, fill, dtype=x.dtype)
    out[:n] = x
    return out


def device_arrays(flat: FlatDILI, dtype=jnp.float64, pad: bool = True) -> dict:
    """Upload the snapshot; pads table lengths to powers of two so republishes
    reuse the compiled search executable.

    Every table is a 1-D column (the traversal, the dense probe, the range
    bisection and the epoch publisher's retrace detection all read these).
    """
    f = flat
    conv = (lambda x, fill: _pad_pow2(x, fill)) if pad else (lambda x, fill: x)
    return dict(
        a=jnp.asarray(conv(np.asarray(f.a), 0.0), dtype),
        b=jnp.asarray(conv(np.asarray(f.b), 0.0), dtype),
        base=jnp.asarray(conv(f.base, 0), jnp.int32),
        fo=jnp.asarray(conv(f.fo, 1), jnp.int32),
        dense=jnp.asarray(conv(f.dense, 0), jnp.int8),
        tag=jnp.asarray(conv(f.tag, TAG_EMPTY), jnp.int8),
        key=jnp.asarray(conv(f.key, 0.0), dtype),
        # payloads keep the snapshot's int64 width — serving payloads (KV slot
        # ids, document offsets) may exceed 2^31 (requires x64; under x32 jax
        # silently narrows, matching the f32 kernel path)
        val=jnp.asarray(conv(f.val, -1), jnp.int64),
        # key-sorted pair table (range queries); +inf pads keep searchsorted
        # honest past the populated prefix.  pair_slot (slot ranks) stays
        # host-side on FlatDILI — no device path reads it.
        pair_key=jnp.asarray(conv(f.pair_key, np.inf), dtype),
        pair_val=jnp.asarray(conv(f.pair_val, -1), jnp.int64),
        root=jnp.int32(f.root),
        max_depth=jnp.int32(f.max_depth),
        # static metadata (host Python bool, stripped before jit): standard
        # DILI builds have no dense leaves at all, so the whole Alg.-1 dense
        # probe (32 fixed gather trips) is skipped unless one exists
        has_dense=bool(np.asarray(f.dense).any()),
    )


def as_snapshot_dict(idx) -> dict:
    """Accept either the raw snapshot dict or an `api.DeviceSnapshot`
    (duck-typed on `.as_dict()`, so `core` never imports `api`).  Every
    public search entry point funnels through here."""
    if isinstance(idx, dict):
        return idx
    return idx.as_dict()


def resolve_max_depth(idx) -> int:
    """The snapshot's true traversal depth, as a static int.

    Every search call site derives its trip count from the snapshot through
    here (or passes a depth it got from `FlatDILI.max_depth` /
    `SnapshotStore.max_depth` / `ShardedDILI.max_depth`) — hard-coded depths
    are a bug.  Raises inside traced code, where the depth must be threaded
    in explicitly as a Python int.
    """
    md = as_snapshot_dict(idx)["max_depth"]
    if isinstance(md, jax.core.Tracer):
        raise TypeError(
            "resolve_max_depth() needs a concrete snapshot; inside jit/"
            "shard_map pass max_depth explicitly as a static Python int")
    return int(md)


def _split_static(idx: dict) -> tuple[dict, bool]:
    """Strip host-static metadata from the snapshot dict before it crosses a
    jit boundary; returns (array-only dict, has_dense).  `has_dense` defaults
    to True (always-correct) when absent or already traced."""
    hd = idx.get("has_dense", True)
    if not isinstance(hd, (bool, np.bool_)):
        hd = True
    if "has_dense" in idx:
        idx = {k: v for k, v in idx.items() if k != "has_dense"}
    return idx, bool(hd)


# ---------------------------------------------------------------------------
# Unified traversal (Algorithm 6 batched)
# ---------------------------------------------------------------------------


def _traverse_step(idx: dict, q, state, with_stats: bool):
    """One level of the unified traversal; shared by the fixed-trip scan and
    the convergence early-exit while_loop."""
    if with_stats:
        n, done, val, found, nodes, probes = state
    else:
        n, done, val, found = state
    a = idx["a"][n]
    b = idx["b"][n]
    fo = idx["fo"][n]
    is_dense = idx["dense"][n] > 0
    pos = predict_slot(a, b, q, fo)
    s = idx["base"][n] + pos
    t = idx["tag"][s]
    sk = idx["key"][s]
    sv = idx["val"][s]
    step_active = ~done & ~is_dense
    is_child = (t == TAG_CHILD) & step_active
    hit = (t == TAG_PAIR) & (sk == q) & step_active
    miss = ((t == TAG_EMPTY) | ((t == TAG_PAIR) & (sk != q))) & step_active
    val = jnp.where(hit, sv, val)
    found = found | hit
    n = jnp.where(is_child, sv.astype(jnp.int32), n)
    done = done | hit | miss | (is_dense & ~done)
    if with_stats:
        nodes = nodes + step_active.astype(jnp.int32)
        probes = probes + step_active.astype(jnp.int32)
        return (n, done, val, found, nodes, probes)
    return (n, done, val, found)


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "with_stats", "early_exit",
                                    "has_dense"))
def _search_batch(idx: dict, queries: jnp.ndarray, max_depth: int,
                  with_stats: bool = False, early_exit: bool = False,
                  has_dense: bool = True):
    q = queries
    # derive carries from q so their varying-manual-axes match inside
    # shard_map bodies (constants would be vma-unvarying and break scan)
    zi = (q * 0).astype(jnp.int32)
    zb = zi > 0
    n0 = zi + idx["root"]

    init = (n0, zb, (zi - 1).astype(idx["val"].dtype), zb)
    if with_stats:
        init = init + (zi, zi)

    if early_exit:
        # convergence early exit: the whole batch stops gathering once every
        # lane is done — a batch bottoming out at height h pays h rounds,
        # not max_depth
        def cond(st):
            return (st[0] < max_depth) & ~jnp.all(st[2])

        def body(st):
            return (st[0] + 1,) + _traverse_step(idx, q, st[1:], with_stats)

        out = jax.lax.while_loop(cond, body, (jnp.int32(0),) + init)
        state = out[1:]
    else:
        def sbody(state, _):
            return _traverse_step(idx, q, state, with_stats), None

        state, _ = jax.lax.scan(sbody, init, None, length=max_depth)

    if with_stats:
        n, done, val, found, nodes, probes = state
    else:
        n, done, val, found = state

    if not has_dense:
        # snapshot has no dense leaves (standard DILI): Algorithm 1's probe
        # phases (32 fixed gather trips) vanish from the computation
        if with_stats:
            return val, found, nodes, probes
        return val, found

    # dense-leaf exit: exponential + binary search (Algorithm 1 lines 2-5)
    is_dense = idx["dense"][n] > 0
    dval, dfound, dprobes = _dense_search(idx, q, n)
    val = jnp.where(is_dense & dfound, dval, val)
    found = found | (is_dense & dfound)
    if with_stats:
        nodes = nodes + is_dense.astype(jnp.int32)
        probes = probes + jnp.where(is_dense, dprobes, 0)
        return val, found, nodes, probes
    return val, found


def search_batch(idx: dict, queries: jnp.ndarray, max_depth: int | None = None,
                 with_stats: bool = False, early_exit: bool = False):
    """Point lookups. Returns (values, found) — values only valid where found.

    `idx` is the device snapshot — either the raw dict or an
    `api.DeviceSnapshot`.  `max_depth=None` derives the trip count from the
    snapshot (`resolve_max_depth`); pass it explicitly only inside traced
    code.  `early_exit=True` swaps the fixed-trip scan for a
    batch-convergence while_loop.  `with_stats` additionally returns
    (nodes_visited, slot_probes) per query — the Table-5 cache-miss proxy
    (each node visit + slot probe = one HBM/cache-line touch in the paper's
    cost model).
    """
    idx = as_snapshot_dict(idx)
    if max_depth is None:
        max_depth = resolve_max_depth(idx)
    idx, has_dense = _split_static(idx)
    return _search_batch(idx, queries, max_depth=max_depth,
                         with_stats=with_stats, early_exit=early_exit,
                         has_dense=has_dense)


def _dense_search(idx: dict, q: jnp.ndarray, n: jnp.ndarray):
    """Vectorized exponential search around the model prediction inside a
    dense leaf [base, base+fo).  Fixed trip counts (14 doubling + 14 binary
    halving cover fo <= 2^14 = 16384 > 2*omega)."""
    a = idx["a"][n]
    b = idx["b"][n]
    fo = idx["fo"][n]
    base = idx["base"][n]
    m1 = jnp.maximum(fo - 1, 0)
    pred = jnp.clip(predict_slot(a, b, q, fo), 0, m1)

    def key_at(i):
        return idx["key"][base + jnp.clip(i, 0, m1)]

    kp = key_at(pred)
    zi = pred * 0
    probes = zi + 1

    # --- exponential phase: grow a distance bound B until it brackets q ----
    going_up = kp < q

    def exp_body(state, _):
        bound, done, probes = state
        up_i = jnp.clip(pred + bound, 0, m1)
        dn_i = jnp.clip(pred - bound, 0, m1)
        need_up = going_up & ~done & (key_at(up_i) < q) & (pred + bound < m1)
        need_dn = ~going_up & ~done & (key_at(dn_i) > q) & (pred - bound > 0)
        probes = probes + (~done).astype(jnp.int32)
        done = done | ~(need_up | need_dn)
        bound = jnp.where(done, bound, bound * 2)
        return (bound, done, probes), None

    (bound, _, probes), _ = jax.lax.scan(
        exp_body, (zi + 1, zi > 0, probes), None, length=16)

    # bracket [lo, hi] guaranteed to contain the lower bound of q
    lo = jnp.where(going_up, pred, jnp.maximum(pred - bound, 0))
    hi = jnp.where(going_up, jnp.minimum(pred + bound, m1), pred)

    # --- binary phase: first index with key >= q ---------------------------
    def bin_body(state, _):
        lo, hi, probes = state
        mid = (lo + hi) // 2
        go = lo < hi
        below = key_at(mid) < q
        lo = jnp.where(go & below, mid + 1, lo)
        hi = jnp.where(go & ~below, mid, hi)
        probes = probes + go.astype(jnp.int32)
        return (lo, hi, probes), None

    (lo, hi, probes), _ = jax.lax.scan(bin_body, (lo, hi, probes), None,
                                       length=16)
    s = base + jnp.clip(lo, 0, m1)
    ok = (idx["tag"][s] == TAG_PAIR) & (idx["key"][s] == q)
    return idx["val"][s], ok, probes


# ---------------------------------------------------------------------------
# Overlay lookup + fused snapshot+overlay search
# ---------------------------------------------------------------------------


def overlay_arrays(ov: DeltaOverlay, dtype=jnp.float64) -> dict:
    # vals stay int64: overlay payloads must round-trip the same width as the
    # snapshot's (int32 silently wrapped payloads above 2^31)
    return dict(keys=jnp.asarray(ov.keys, dtype),
                vals=jnp.asarray(ov.vals, jnp.int64))


@jax.jit
def overlay_lookup(ov: dict, queries: jnp.ndarray):
    i = jnp.searchsorted(ov["keys"], queries)
    i = jnp.clip(i, 0, len(ov["keys"]) - 1)
    found = ov["keys"][i] == queries
    return ov["vals"][i], found


def resolve_overlay(ov: dict, queries: jnp.ndarray, snap_vals: jnp.ndarray,
                    snap_found: jnp.ndarray):
    """Fuse overlay state over snapshot results: an overlay hit wins, and an
    overlay tombstone (``ov["tomb"][i] != 0``) hides a snapshot hit.  `ov`
    without a "tomb" entry behaves as the legacy insert-only overlay."""
    i = jnp.clip(jnp.searchsorted(ov["keys"], queries),
                 0, len(ov["keys"]) - 1)
    hit = ov["keys"][i] == queries
    tomb = ov.get("tomb")
    dead = hit & (tomb[i] > 0) if tomb is not None else hit & False
    live = hit & ~dead
    val = jnp.where(live, ov["vals"][i], snap_vals)
    return val, live | (snap_found & ~dead)


def _search_with_overlay(idx: dict, ov: dict, queries: jnp.ndarray,
                         max_depth: int, early_exit: bool, has_dense: bool):
    v0, f0 = _search_batch(idx, queries, max_depth=max_depth,
                           early_exit=early_exit, has_dense=has_dense)
    return resolve_overlay(ov, queries, v0, f0)


_swo = jax.jit(_search_with_overlay, static_argnums=(3, 4, 5))
_swo_donated = jax.jit(_search_with_overlay, static_argnums=(3, 4, 5),
                       donate_argnums=(2,))


def search_with_overlay(idx: dict, ov: dict, queries: jnp.ndarray,
                        max_depth: int | None = None, *,
                        early_exit: bool = True,
                        donate_queries: bool = False):
    """ONE fused jitted dispatch: snapshot traversal + overlay searchsorted,
    resolving overlay-hit / overlay-tombstone / snapshot-hit (DESIGN.md
    section 8).  The overlay (recent writes) wins over the snapshot;
    tombstones hide snapshot hits.

    `donate_queries=True` donates the query buffer to the computation (the
    caller must not reuse it) — skipped on CPU, which does not support
    donation.  This is the serving read path: `SessionTable`/`OnlineIndex`
    and the per-shard distributed reads route through it, so a query batch
    costs one device dispatch, not a traversal dispatch plus an overlay
    round-trip.
    """
    idx = as_snapshot_dict(idx)
    if max_depth is None:
        max_depth = resolve_max_depth(idx)
    idx, has_dense = _split_static(idx)
    donate = donate_queries and jax.default_backend() != "cpu"
    fn = _swo_donated if donate else _swo
    return fn(idx, ov, queries, max_depth, early_exit, has_dense)


# ---------------------------------------------------------------------------
# Range query: bisect the sorted pair table, gather one bounded window
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_hits",))
def _range_query(idx: dict, lo: jnp.ndarray, hi: jnp.ndarray, max_hits: int):
    pk = idx["pair_key"]
    start = jnp.searchsorted(pk, lo, side="left")           # [Q]
    end = jnp.searchsorted(pk, hi, side="left")             # [Q]
    cnt = jnp.maximum(end - start, 0)
    offs = jnp.arange(max_hits)                             # [H]
    valid = offs[None, :] < cnt[:, None]                    # [Q, H]
    g = jnp.clip(start[:, None] + offs[None, :], 0, pk.shape[0] - 1)
    ks = jnp.where(valid, pk[g], jnp.inf)
    vs = jnp.where(valid, idx["pair_val"][g], -1)
    return ks, vs, jnp.minimum(cnt, max_hits).astype(jnp.int32)


def range_query_batch(idx: dict, lo: jnp.ndarray, hi: jnp.ndarray,
                      max_hits: int = 128):
    """For each (lo, hi): the first max_hits pair (key, val)s in [lo, hi),
    ascending, plus the count (saturating at max_hits).

    Two searchsorted bisections of the flatten()-time key-sorted pair table
    locate the window, then ONE bounded gather reads it — O(log n + max_hits)
    per query.  (The previous implementation mask-scanned the entire global
    slot table per query pair: O(n_slots), because DILI's entry arrays are
    not densely packed — Fig. 6b discussion.  The pair table densifies them
    once per publish instead.)
    """
    idx = as_snapshot_dict(idx)
    idx = {k: idx[k] for k in ("pair_key", "pair_val")}
    return _range_query(idx, lo, hi, max_hits=max_hits)


# retrace watchdog: expose per-entry-point traced-executable counts so
# `metrics()["retrace"]["jit_cache_entries"]` can attribute a retrace storm
# to the executable that grew (DESIGN.md section 13)
watchdog.register_jit("search.search_batch", _search_batch)
watchdog.register_jit("search.overlay_lookup", overlay_lookup)
watchdog.register_jit("search.search_with_overlay", _swo)
watchdog.register_jit("search.search_with_overlay_donated", _swo_donated)
watchdog.register_jit("search.range_query", _range_query)


# ---------------------------------------------------------------------------
# Convenience host wrapper
# ---------------------------------------------------------------------------


def lookup_np(idx: dict, queries: np.ndarray, max_depth: int | None = None):
    v, f = search_batch(idx, jnp.asarray(queries), max_depth,
                        early_exit=True)
    return np.asarray(v), np.asarray(f)
