"""The three pluggable engines behind `repro.api.LearnedIndex`.

Every engine speaks the same `Engine` protocol — lookup / range / upsert /
delete / flush / items / stats — over the same logical contract (exact
results at every point in time, deletes visible before any merge), but maps
it to a different execution substrate:

  * `LocalEngine`   — single-process XLA: the fused snapshot+overlay search
    (`core.search.search_with_overlay`) over an epoch-published
    `DeviceSnapshot`, writes through `repro.online.OnlineIndex`'s
    overlay/merge lifecycle.
  * `PallasEngine`  — f32 keys, VMEM-tiled Pallas kernel dispatch with the
    XLA fallback (`kernels.ops.dili_search`); the snapshot is built under
    `placement_dtype(np.float32)` so construction and kernel arithmetic
    agree (DESIGN.md section 7).
  * `ShardedEngine` — range-partitioned mesh index (`core.distributed`):
    per-shard overlays, single-shard merges, fused in-shard overlay
    resolution, collective lookups/ranges under `shard_map`.

Range queries are overlay-exact on every engine: the device bisects the
key-sorted pair table with enough headroom to cover pending tombstones,
then the (small, sorted) overlay window is merged host-side per query.
"""

from __future__ import annotations

import time
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from ..core import search as S
from ..core.dili import bulk_load, placement_dtype
from ..core.distributed import (build_sharded, combined_overlay_arrays,
                                sharded_delete, sharded_lookup,
                                sharded_merge, sharded_range_query,
                                sharded_upsert, shard_of, to_mesh)
from ..core.flat import flatten, merge_sorted_runs
from ..maintain import (IncrementalFlattener, LeafAccounting,
                        fold_with_accounting, run_reclusters, run_retrains)
from ..obs import Telemetry, watchdog
from ..online.merge import OnlineIndex, adjust_pressure
from ..online.overlay import (TombstoneOverlay, fold_overlay,
                              overlay_device_arrays)
from .config import IndexConfig
from .snapshot import DeviceSnapshot


@runtime_checkable
class Engine(Protocol):
    """What a `LearnedIndex` backend must provide.  All key/value inputs and
    outputs are host numpy; engines own their device placement."""

    name: str

    def lookup(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(vals, found) for a batch of point queries."""
        ...

    def range(self, lo: np.ndarray, hi: np.ndarray,
              max_hits: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """First `max_hits` live pairs in each [lo, hi), ascending:
        (keys [Q,H] +inf-padded, vals [Q,H] -1-padded, counts [Q])."""
        ...

    def upsert(self, keys: np.ndarray, vals: np.ndarray) -> None: ...

    def delete(self, keys: np.ndarray) -> None: ...

    def flush(self) -> None:
        """Fold every pending write through the host tree and republish."""
        ...

    def get(self, key: float) -> int | None: ...

    def items(self) -> tuple[np.ndarray, np.ndarray]:
        """The full live (keys, vals) set, key-sorted, overlay applied."""
        ...

    def stats(self) -> dict: ...

    def close(self) -> None:
        """Release engine resources (e.g. the background maintenance
        worker); pending writes stay readable.  Idempotent."""
        ...

    def maint_timings(self) -> list[dict]:
        """Per-merge wall times: merge_s (fold+retrain+flatten),
        publish_s (upload+flip), incremental, dirty_frac."""
        ...


# ---------------------------------------------------------------------------
# shared overlay-exact helpers
# ---------------------------------------------------------------------------


def _merged_items(snap_k: np.ndarray, snap_v: np.ndarray, ov_k: np.ndarray,
                  ov_v: np.ndarray, ov_t: np.ndarray):
    """Apply overlay entries over the key-sorted snapshot pair run and drop
    tombstones — the logical content of the index, independent of engine."""
    mk, (mv, mt) = merge_sorted_runs(
        np.asarray(snap_k, np.float64),
        (np.asarray(snap_v, np.int64), np.zeros(len(snap_k), np.int8)),
        np.asarray(ov_k, np.float64),
        (np.asarray(ov_v, np.int64), np.asarray(ov_t, np.int8)))
    live = mt == 0
    return mk[live], mv[live]


def _overlay_summary(overlays) -> dict:
    """The engine-independent overlay slice of `stats()`: every engine
    reports the same keys with the same meanings (equivalence is pinned by
    tests/test_api_engines.py).  `pending_writes` counts distinct pending
    keys (live + tombstones) across all overlays; `overlay_fill` is the
    worst single overlay's fill fraction — the number the merge policy's
    max_fill trigger actually compares against."""
    ovs = list(overlays)
    count = sum(ov.count for ov in ovs)
    tombs = sum(ov.n_tombstones for ov in ovs)
    return dict(pending_writes=count,
                overlay_live=count - tombs,
                overlay_tombstones=tombs,
                overlay_cap=sum(ov.cap for ov in ovs),
                overlay_fill=max((ov.full_fraction for ov in ovs),
                                 default=0.0))


def _maint_summary(*, n_full: int, n_incremental: int, n_retrains: int,
                   dirty_row_fraction: float, queue_depth: int = 0,
                   errors: int = 0, n_reclusters: int = 0,
                   n_forced_full: int = 0) -> dict:
    """The engine-independent maintenance slice of `stats()` (pinned by
    tests/test_api_engines.py): flatten kind counts, subtree retrains and
    locality re-clusters, the last merge's dirty-row fraction, the
    background queue depth (0 on engines without a scheduler), and the
    forced-full-flatten count (`n_forced_full_flattens`: full re-flattens
    the incremental flattener was FORCED into by an unmappable dirty id —
    distinct from intentional full flattens, nonzero means the O(dirty)
    guarantee silently degraded)."""
    return dict(n_full_flattens=n_full, n_incremental_flattens=n_incremental,
                n_retrains=n_retrains, n_reclusters=n_reclusters,
                n_forced_full_flattens=n_forced_full,
                dirty_row_fraction=dirty_row_fraction,
                maint_queue_depth=queue_depth, maint_errors=errors)


class EngineTelemetryBase:
    """Shared `stats()` / `maint_timings()` / `metrics()` for every engine.

    The three engines used to carry near-identical copies of the stats
    dict assembly; this base composes the engine-independent pieces —
    `_overlay_summary`, the `_maint_summary` maintenance counters, and
    the telemetry accounting — from five small per-engine hooks:

      _stats_extra()      engine-specific keys (snapshot sizing, shard
                          breakdowns, kernel eligibility, ...)
      _stats_overlays()   the overlay objects summarized for pending-write
                          accounting (deduped during background merges)
      _timing_rows()      per-merge wall-time rows (build publish excluded)
      _queue_depth()      background scheduler depth (0 without one)
      _maint_error_list() background task failures (empty without one)

    Engines must also expose: name, epoch, telemetry, n_flattens,
    n_merges, n_full_flattens, n_incremental_flattens, n_retrains,
    last_dirty_frac.
    """

    telemetry: Telemetry

    #: locality re-cluster count; engines with the maintenance subsystem
    #: override (property or instance counter)
    n_reclusters: int = 0

    def _n_forced_full_flattens(self) -> int:
        """Unmappable-dirty-id fallbacks across the engine's flatteners."""
        return 0

    def _stats_extra(self) -> dict:
        return {}

    def _queue_depth(self) -> int:
        return 0

    def _maint_error_list(self) -> list:
        return []

    def _maint_degraded(self) -> bool:
        """Background retries exhausted -> merges run synchronously now
        (only the local engine's scheduler path can degrade)."""
        return False

    def close(self) -> None:
        pass

    # -- durability hooks (DESIGN.md section 14) ------------------------------

    #: shards the WAL fans out over (1 everywhere but the sharded engine)
    n_wal_shards: int = 1

    def shard_ids(self, keys: np.ndarray) -> np.ndarray:
        """WAL shard routing for a write batch (all shard 0 on
        single-shard engines)."""
        return np.zeros(len(np.atleast_1d(keys)), np.int64)

    _on_publish = None

    def set_on_publish(self, cb) -> None:
        """Register a post-merge-publish callback (the durability manager
        checkpoints through it).  Runs on whichever thread published."""
        self._on_publish = cb

    def _notify_publish(self) -> None:
        if self._on_publish is not None:
            self._on_publish()

    def stats(self) -> dict:
        errors = self._maint_error_list()
        return dict(engine=self.name, epoch=self.epoch,
                    **self._stats_extra(),
                    **_overlay_summary(self._stats_overlays()),
                    n_flattens=self.n_flattens, n_merges=self.n_merges,
                    **_maint_summary(
                        n_full=self.n_full_flattens,
                        n_incremental=self.n_incremental_flattens,
                        n_retrains=self.n_retrains,
                        dirty_row_fraction=self.last_dirty_frac,
                        queue_depth=self._queue_depth(),
                        errors=len(errors),
                        n_reclusters=self.n_reclusters,
                        n_forced_full=self._n_forced_full_flattens()),
                    maint_degraded=self._maint_degraded(),
                    maint_error_logs=list(errors),
                    telemetry_enabled=self.telemetry.enabled,
                    ops_total=self.telemetry.ops_total)

    def maint_timings(self) -> list[dict]:
        """Per-merge wall times: merge_s (fold+retrain+flatten),
        publish_s (upload+flip), incremental, dirty_frac."""
        return self._timing_rows()

    def metrics(self) -> dict:
        """The stable JSON-able telemetry snapshot (same schema on every
        engine; DESIGN.md section 13)."""
        return dict(engine=self.name, **self.telemetry.snapshot())

    # -- index-health introspection (obs.inspect) -----------------------------

    def _inspect_flats(self) -> list:
        """Published FlatDILI snapshot(s), one per shard."""
        raise NotImplementedError

    def _inspect_flatteners(self) -> list:
        """Live IncrementalFlattener instances ([] = maintenance off)."""
        return []

    def _inspect_accounts(self) -> list:
        """Live LeafAccounting instances ([] = accounting off)."""
        return []

    def inspect(self) -> dict:
        """The engine-independent `dili.inspect/1` health document; the
        facade layers the WAL footprint on top."""
        from ..obs.inspect import build_inspect
        accounts = []
        for acct in self._inspect_accounts():
            accounts.extend(acct.accounts())
        ov = _overlay_summary(self._stats_overlays())
        return build_inspect(
            engine=self.name, epoch=self.epoch,
            flats=self._inspect_flats(),
            flatteners=self._inspect_flatteners(),
            accounts=accounts,
            overlay=dict(pending=ov["pending_writes"],
                         live=ov["overlay_live"],
                         tombstones=ov["overlay_tombstones"],
                         cap=ov["overlay_cap"],
                         fill=ov["overlay_fill"]))


def _require_x64(cfg: IndexConfig) -> None:
    """A 64-bit key dtype needs `jax_enable_x64`: without it jax narrows
    f64 keys and int64 payloads to 32 bits without an error."""
    if (np.dtype(cfg.resolved_dtype).itemsize == 8
            and not jax.config.jax_enable_x64):
        raise ValueError(
            f"the {cfg.engine} engine's {np.dtype(cfg.resolved_dtype).name} "
            f"keys need jax_enable_x64 (set JAX_ENABLE_X64=1 before jax is "
            f"imported); without it jax truncates them to 32 bits")


def _reject_background(cfg: IndexConfig, engine: str) -> None:
    if cfg.maintenance is not None and cfg.maintenance.background:
        raise ValueError(
            f"background maintenance requires the local engine (its "
            f"double-buffered SnapshotStore); the {engine} engine "
            f"supports maintenance=MaintenanceConfig(background=False)")


def _merge_range_windows(ks, vs, cnt, lo, hi, ov_k, ov_v, ov_t,
                         max_hits: int):
    """Resolve overlay state over per-query snapshot range windows.

    `ks/vs/cnt` are the device results (ascending prefix per query, counts
    saturating at the fetched window size, which includes tombstone
    headroom).  Each query merges its overlay slice [lo, hi) last-write-wins
    and truncates back to `max_hits`.  O(Q * (window + overlay-slice)) on
    the host — the overlay is small by construction (it merges away)."""
    q_n = len(cnt)
    out_k = np.full((q_n, max_hits), np.inf)
    out_v = np.full((q_n, max_hits), -1, np.int64)
    out_c = np.zeros(q_n, np.int32)
    ks = np.asarray(ks, np.float64)
    vs = np.asarray(vs, np.int64)
    starts = np.searchsorted(ov_k, lo, side="left")
    ends = np.searchsorted(ov_k, hi, side="left")
    for i in range(q_n):
        mk, mv = _merged_items(ks[i][: cnt[i]], vs[i][: cnt[i]],
                               ov_k[starts[i]: ends[i]],
                               ov_v[starts[i]: ends[i]],
                               ov_t[starts[i]: ends[i]])
        c = min(len(mk), max_hits)
        out_k[i, :c] = mk[:c]
        out_v[i, :c] = mv[:c]
        out_c[i] = c
    return out_k, out_v, out_c


@jax.jit
def _pair_table_recheck(pk, pv, q, v, f):
    """Comparison-exact patch for point-lookup miss lanes.

    Compiled XLA may evaluate `a + b*q` with a SINGLE rounding (FMA-style
    contraction survives the optimization_barrier on the f32 path), while
    construction placed keys with numpy's two roundings; at key magnitudes
    where f32 ULP-safety is unattainable (DESIGN.md section 7) a boundary
    query can then mis-route by one child and miss.  Found lanes are always
    true hits (tag + key equality), so only misses need the O(log n)
    bisection of the key-sorted pair table.  Also returns how many lanes
    it patched: a search that misroutes more than rarely shows there."""
    i = jnp.clip(jnp.searchsorted(pk, q), 0, pk.shape[0] - 1)
    hit = pk[i] == q
    patched = jnp.sum(hit & ~f, dtype=jnp.int32)
    return jnp.where(f, v, jnp.where(hit, pv[i], v)), f | hit, patched


watchdog.register_jit("api.pair_table_recheck", _pair_table_recheck)


def _tombstone_headroom(ov_k, ov_t, lo, hi) -> int:
    """Extra snapshot rows the device window must fetch so that dropping
    tombstoned keys still leaves `max_hits` live candidates: the maximum
    number of pending tombstones falling inside any queried window."""
    tk = ov_k[np.asarray(ov_t) > 0]
    if len(tk) == 0:
        return 0
    return int(np.max(np.searchsorted(tk, hi, side="left")
                      - np.searchsorted(tk, lo, side="left")))


def _truncate_windows(ks, vs, cnt, max_hits: int):
    """No-overlay fast path: clip device windows fetched with headroom back
    to `max_hits` without a host merge."""
    ks = np.asarray(ks, np.float64)[:, :max_hits]
    vs = np.asarray(vs, np.int64)[:, :max_hits]
    cnt = np.minimum(np.asarray(cnt, np.int32), max_hits)
    pos = np.arange(max_hits)[None, :]
    ks = np.where(pos < cnt[:, None], ks, np.inf)
    vs = np.where(pos < cnt[:, None], vs, -1)
    return ks, vs, cnt


def _overlay_exact_range(entries, lo, hi, max_hits: int, device_range):
    """The one overlay-exact range recipe every engine shares: size the
    device fetch with tombstone headroom, bisect on the device via
    `device_range(lo, hi, fetch)`, then either truncate (no pending writes)
    or merge each query's overlay slice host-side."""
    ov_k, ov_v, ov_t = entries
    fetch = max_hits + _tombstone_headroom(ov_k, ov_t, lo, hi)
    if fetch > max_hits:
        # pow2-quantize the over-fetch: headroom varies batch to batch under
        # write-heavy mixes and every distinct fetch is a fresh executable;
        # extra rows are clipped by the truncate/merge step below, so the
        # result is identical
        fetch = max_hits + (1 << (fetch - max_hits - 1).bit_length())
    ks, vs, cnt = device_range(lo, hi, fetch)
    ks, vs, cnt = np.asarray(ks), np.asarray(vs), np.asarray(cnt)
    if len(ov_k) == 0:
        return _truncate_windows(ks, vs, cnt, max_hits)
    return _merge_range_windows(ks, vs, cnt, lo, hi, ov_k, ov_v, ov_t,
                                max_hits)


# ---------------------------------------------------------------------------
# LocalEngine
# ---------------------------------------------------------------------------


class LocalEngine(EngineTelemetryBase):
    """Single-process engine over the online-update lifecycle: writes land
    in the tombstone overlay, reads are ONE fused device dispatch, merges
    follow the configured `MergePolicy` (DESIGN.md section 8-9)."""

    name = "local"

    def __init__(self, keys: np.ndarray, vals: np.ndarray, cfg: IndexConfig):
        _require_x64(cfg)
        self.cfg = cfg
        self.telemetry = Telemetry(enabled=cfg.telemetry)
        self.oi = OnlineIndex(keys, vals, policy=cfg.merge,
                              overlay_cap=cfg.overlay_cap,
                              dtype=cfg.resolved_dtype, pad=cfg.pad,
                              early_exit=cfg.early_exit,
                              maintenance=cfg.maintenance,
                              telemetry=self.telemetry,
                              **cfg.bulk_load_kw())

    # -- reads --------------------------------------------------------------

    def lookup(self, queries):
        return self.oi.lookup(queries)

    def range(self, lo, hi, max_hits):
        dt = self.oi.store.dtype
        # pending entries captured BEFORE the snapshot is read inside the
        # lambda: exact across a concurrent background publish
        return _overlay_exact_range(
            self.oi.pending_entries(), lo, hi, max_hits,
            lambda lo_, hi_, fetch: S.range_query_batch(
                self.oi.store.idx, jnp.asarray(lo_, dt),
                jnp.asarray(hi_, dt), max_hits=fetch))

    def get(self, key: float):
        return self.oi.get(key)

    @property
    def snapshot(self):
        """The current epoch's `DeviceSnapshot` (read-only composition with
        `core.search`; pending overlay writes are NOT in it)."""
        return self.oi.store.idx

    # -- writes -------------------------------------------------------------

    def upsert(self, keys, vals):
        self.oi.upsert_batch(keys, vals)

    def delete(self, keys):
        self.oi.delete_batch(keys)

    def flush(self):
        self.oi.flush()

    def close(self):
        self.oi.close()

    def set_on_publish(self, cb) -> None:
        # the OnlineIndex fires it itself at the end of every merge
        # pipeline run (writer thread or maintenance worker)
        self.oi.on_publish = cb

    def _maint_degraded(self) -> bool:
        return self.oi.maint_degraded

    def _inspect_flats(self) -> list:
        return [self.oi.store.flat]

    def _inspect_flatteners(self) -> list:
        fl = self.oi.flattener
        return [] if fl is None else [fl]

    def _inspect_accounts(self) -> list:
        acct = self.oi.accounting
        return [] if acct is None else [acct]

    # -- introspection ------------------------------------------------------

    def items(self):
        # pending entries BEFORE the flat (exact across a background flip)
        ok, ovv, ott = self.oi.pending_entries()
        f = self.oi.store.flat
        return _merged_items(f.pair_key, f.pair_val, ok, ovv, ott)

    @property
    def host(self):
        return self.oi.dili

    @property
    def epoch(self) -> int:
        return self.oi.epoch

    @property
    def n_flattens(self) -> int:
        return self.oi.n_flattens

    @property
    def n_merges(self) -> int:
        return self.oi.n_merges

    @property
    def n_full_flattens(self) -> int:
        return self.oi.n_full_flattens

    @property
    def n_incremental_flattens(self) -> int:
        return self.oi.n_incremental_flattens

    @property
    def n_retrains(self) -> int:
        return self.oi.n_retrains

    @property
    def n_reclusters(self) -> int:
        return self.oi.n_reclusters

    def _n_forced_full_flattens(self) -> int:
        fl = self.oi.flattener
        return 0 if fl is None else fl.n_fallback_full

    @property
    def last_dirty_frac(self) -> float:
        return self.oi.last_dirty_frac

    def _timing_rows(self) -> list[dict]:
        return [dict(merge_s=st.merge_s, publish_s=st.publish_s,
                     incremental=st.incremental, dirty_frac=st.dirty_frac)
                for st in self.oi.store.history[1:]]

    def _stats_overlays(self):
        # during an in-flight background merge, summarize the DEDUPED view
        # (a key rewritten after the freeze lives in both overlays but is
        # one distinct pending key — _overlay_summary's contract)
        oi = self.oi
        pend = oi._merging
        return [oi.overlay] if pend is None else [pend.merged_with(oi.overlay)]

    def _queue_depth(self) -> int:
        sched = self.oi.scheduler
        return 0 if sched is None else sched.depth

    def _maint_error_list(self) -> list:
        sched = self.oi.scheduler
        return [] if sched is None else list(sched.errors)

    def _stats_extra(self) -> dict:
        snap = self.oi.store.idx
        return dict(max_depth=snap.max_depth,
                    snapshot_keys=int(self.oi.store.flat.n_pairs),
                    merge_reasons=dict(self.oi.merge_reasons),
                    device_bytes=snap.nbytes)


# ---------------------------------------------------------------------------
# PallasEngine
# ---------------------------------------------------------------------------


class PallasEngine(EngineTelemetryBase):
    """f32 kernel engine: lookups dispatch to the Pallas kernel when the
    tables fit the configured VMEM budget (XLA fallback otherwise / for
    flagged lanes), ranges bisect an f32 `DeviceSnapshot`.  Keys are
    quantized to f32 at the boundary — duplicates after the cast collapse
    last-write-wins, the documented f32 tolerance rule."""

    name = "pallas"

    def __init__(self, keys: np.ndarray, vals: np.ndarray, cfg: IndexConfig):
        from ..kernels import ops as K
        self._K = K
        self.cfg = cfg
        self.telemetry = Telemetry(enabled=cfg.telemetry)
        _reject_background(cfg, self.name)
        m = cfg.maintenance
        self.flattener = (IncrementalFlattener()
                          if m is not None and m.incremental else None)
        self.accounting = (LeafAccounting(m)
                           if m is not None and (m.retrain or m.recluster)
                           else None)
        k32, v64 = self._quantize(keys, vals)
        with placement_dtype(np.float32):
            self.dili = bulk_load(k32, v64, **cfg.bulk_load_kw())
        self.overlay = TombstoneOverlay.empty(cfg.overlay_cap)
        self._ov_mirror = None          # device overlay, rebuilt on write
        self.epoch = 0
        self.n_flattens = 0
        self.n_full_flattens = 0
        self.n_incremental_flattens = 0
        self.n_merges = 0
        self.n_retrains = 0
        self.n_reclusters = 0
        self.last_dirty_frac = 1.0
        self._timings: list[dict] = []
        self._writes_since_publish = 0
        self._writes_since_pressure = 0
        # lookup batches per dispatch route (kernels.ops.ROUTE_*), and the
        # lanes the pair-table recheck had to patch after the search
        self.routes: dict[str, int] = {}
        self.recheck_patched_lanes = 0
        self._publish()

    def _n_forced_full_flattens(self) -> int:
        return 0 if self.flattener is None else self.flattener.n_fallback_full

    @staticmethod
    def _check_vals_i32(vals: np.ndarray) -> np.ndarray:
        """The kernel path stores payloads as int32 (deliberately — DESIGN.md
        section 2); reject out-of-range vals instead of silently wrapping."""
        vals = np.asarray(vals, np.int64)
        if len(vals) and (vals.max() >= 2**31 or vals.min() < -(2**31)):
            raise ValueError(
                "pallas engine payloads must fit int32 (the kernel's "
                "payload width); use the local or sharded engine for "
                ">=2^31 vals")
        return vals

    def _quantize(self, keys, vals) -> tuple[np.ndarray, np.ndarray]:
        """Cast keys to f32; collapse post-cast duplicates last-write-wins.

        Build-time collisions are tolerated but no longer silent: in
        magnitude-dense regions (integer keys with |key| >= 2**24, where
        f32 spacing exceeds 1) distinct input keys alias to one f32 value
        and their payloads collapse — a lossy build the caller must be
        able to see coming before queries return "wrong" neighbors.
        Routed through the registry's rate-limited structured warning:
        the `warn.pallas_f32_collision` counter accumulates the collapsed
        count across builds while the Python warning fires once, so a
        flood of lossy rebuilds stays visible but bounded."""
        k32 = np.asarray(keys, np.float64).astype(np.float32)
        order = np.argsort(k32, kind="stable")
        k32, vals = k32[order], self._check_vals_i32(vals)[order]
        keep = np.ones(len(k32), bool)
        keep[:-1] = k32[:-1] != k32[1:]          # keep the LAST duplicate
        n_collapsed = int((~keep).sum())
        if n_collapsed:
            self.telemetry.metrics.warn(
                "pallas_f32_collision",
                f"pallas engine: {n_collapsed} of {len(k32)} build keys "
                f"collide after f32 quantization and were collapsed "
                f"last-write-wins. The kernel's f32 key domain represents "
                f"integers exactly only for |key| < 2**24 (16777216); "
                f"beyond that, adjacent keys closer than one f32 ulp alias "
                f"to the same value. Use the local or sharded engine for "
                f"full f64 key precision.", count=n_collapsed)
        return k32[keep].astype(np.float64), vals[keep]

    def _publish(self, merge_s: float = 0.0):
        t0 = time.perf_counter()
        with self.telemetry.span("merge.flatten"):
            if self.flattener is not None:
                self.flat = self.flattener.flatten(self.dili,
                                                   self.dili.take_dirty())
                incremental = self.flattener.last_incremental
                self.last_dirty_frac = (
                    self.flattener.last_dirty_rows
                    / max(self.flattener.last_total_rows, 1))
            else:
                self.flat = flatten(self.dili)
                self.dili.take_dirty()  # drain (unbounded growth otherwise)
                incremental = False
                self.last_dirty_frac = 1.0
        fl = self.flattener
        self.telemetry.sample_publish(
            n_segments=self.flat.n_segments,
            dirty_rows=(fl.last_dirty_rows if fl is not None
                        else self.flat.n_slots),
            total_rows=(fl.last_total_rows if fl is not None
                        else self.flat.n_slots))
        merge_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        with self.telemetry.span("merge.publish"):
            self.arrs = self._K.kernel_arrays(self.flat)
            self.snap = DeviceSnapshot.from_flat(self.flat, dtype=jnp.float32,
                                                 pad=self.cfg.pad)
            jax.block_until_ready(self.snap.arrays)
        self.n_flattens += 1
        if incremental:
            self.n_incremental_flattens += 1
        else:
            self.n_full_flattens += 1
        if self.epoch > 0:          # the build publish is not a merge row
            self._timings.append(dict(merge_s=merge_s,
                                      publish_s=time.perf_counter() - t0,
                                      incremental=incremental,
                                      dirty_frac=self.last_dirty_frac))
        self.epoch += 1

    # -- reads --------------------------------------------------------------

    def lookup(self, queries):
        q32 = jnp.asarray(np.asarray(queries, np.float64), jnp.float32)
        v, f = self._K.dili_search(self.arrs, q32,
                                   interpret=self.cfg.interpret,
                                   vmem_budget=self.cfg.vmem_budget_bytes,
                                   routes=self.routes)
        v, f, patched = _pair_table_recheck(self.snap.arrays["pair_key"],
                                            self.snap.arrays["pair_val"],
                                            q32, v, f)
        self.recheck_patched_lanes += int(patched)
        if self.overlay.count:
            if self._ov_mirror is None:
                self._ov_mirror = overlay_device_arrays(self.overlay,
                                                        jnp.float32)
            v, f = S.resolve_overlay(self._ov_mirror, q32, v, f)
        return np.asarray(v, np.int64), np.asarray(f, bool)

    def range(self, lo, hi, max_hits):
        lo32 = np.asarray(lo, np.float64).astype(np.float32)
        hi32 = np.asarray(hi, np.float64).astype(np.float32)
        return _overlay_exact_range(
            self.overlay.entries(), lo32, hi32, max_hits,
            lambda lo_, hi_, fetch: S.range_query_batch(
                self.snap, jnp.asarray(lo_, jnp.float32),
                jnp.asarray(hi_, jnp.float32), max_hits=fetch))

    def get(self, key: float):
        k = float(np.float32(key))
        state, v = self.overlay.get(k)
        if state == 0:                      # LIVE
            return v
        if state == 1:                      # TOMBSTONE
            return None
        # the host walk must predict in the precision the tree was placed in
        with placement_dtype(np.float32):
            return self.dili.search(k)

    # -- writes -------------------------------------------------------------

    def _quantize_keys(self, keys) -> np.ndarray:
        """f32-quantize write keys (the documented tolerance rule) — but
        REJECT integer-valued keys the cast moves.  At |key| >= 2**24 the
        f32 spacing exceeds 1, so adjacent int64 keys alias to one f32
        value and the write would silently land on a DIFFERENT logical key
        (a wrong-neighbor corruption, not a rounding tolerance).
        Fractional keys stay under the quantize-to-f32 tolerance the
        engine documents."""
        k64 = np.atleast_1d(np.asarray(keys, np.float64))
        k32 = k64.astype(np.float32).astype(np.float64)
        moved = (k32 != k64) & (np.floor(k64) == k64) & np.isfinite(k64)
        if moved.any():
            raise ValueError(
                f"pallas engine: integer key {k64[moved][0]!r} is not "
                f"exactly representable in the kernel's f32 key domain "
                f"(integers are exact only for |key| < 2**24 = 16777216; "
                f"above that f32 spacing exceeds 1 and adjacent keys "
                f"alias) — the write would land on {k32[moved][0]!r}, a "
                f"different logical key. Use the local or sharded engine "
                f"for int64 keys at this magnitude.")
        return k32

    def upsert(self, keys, vals):
        # overlay reads resolve in int64, but a merge folds these into the
        # int32 kernel tables — enforce the width before accepting the write
        vals = self._check_vals_i32(np.atleast_1d(np.asarray(vals)))
        self.overlay = self.overlay.upsert_batch(self._quantize_keys(keys),
                                                 vals)
        self._ov_mirror = None
        self._note_writes(len(np.atleast_1d(keys)))

    def delete(self, keys):
        self.overlay = self.overlay.delete_batch(self._quantize_keys(keys))
        self._ov_mirror = None
        self._note_writes(len(np.atleast_1d(keys)))

    def _note_writes(self, n: int):
        self._writes_since_publish += n
        self._writes_since_pressure += n
        p = self.cfg.merge
        trigger = (self.overlay.full_fraction >= p.max_fill
                   or self._writes_since_publish >= p.max_writes)
        if not trigger and self._writes_since_pressure >= p.pressure_check_every:
            self._writes_since_pressure = 0
            with placement_dtype(np.float32):   # leaf walk predicts in f32
                trigger = (adjust_pressure(self.dili, self.overlay,
                                           p.pressure_min_pending)
                           > p.pressure_lambda)
        if trigger:
            self.flush()

    def flush(self):
        if self.overlay.count == 0:
            return
        t0 = time.perf_counter()
        tel = self.telemetry
        # the host walk (and any retrain's bulk_load) must place slots in
        # the same f32 arithmetic the kernel searches with
        with placement_dtype(np.float32):
            if self.accounting is not None:
                with tel.span("merge.fold"):
                    fold_with_accounting(self.dili, self.overlay,
                                         self.accounting)
                with tel.span("merge.retrain"):
                    self.n_retrains += run_retrains(self.dili,
                                                    self.accounting)
                # still inside placement_dtype: split_leaf's child models
                # must place slots in the kernel's f32 arithmetic
                with tel.span("merge.recluster"):
                    r = run_reclusters(self.dili, self.accounting,
                                       self.flattener)
                if r:
                    self.n_reclusters += r
                    if tel.enabled:
                        tel.metrics.count("maint.reclusters", r)
            else:
                with tel.span("merge.fold"):
                    fold_overlay(self.dili, self.overlay)
        self.overlay = TombstoneOverlay.empty(self.cfg.overlay_cap)
        self._ov_mirror = None
        self.n_merges += 1
        self._writes_since_publish = 0
        self._writes_since_pressure = 0
        self._publish(merge_s=time.perf_counter() - t0)
        self._notify_publish()

    # -- introspection ------------------------------------------------------

    def items(self):
        ok, ovv, ott = self.overlay.entries()
        return _merged_items(self.flat.pair_key, self.flat.pair_val,
                             ok, ovv, ott)

    @property
    def host(self):
        return self.dili

    @property
    def snapshot(self):
        return self.snap

    def _timing_rows(self) -> list[dict]:
        return list(self._timings)

    def _stats_overlays(self):
        return [self.overlay]

    def _inspect_flats(self) -> list:
        return [self.flat]

    def _inspect_flatteners(self) -> list:
        return [] if self.flattener is None else [self.flattener]

    def _inspect_accounts(self) -> list:
        return [] if self.accounting is None else [self.accounting]

    def _stats_extra(self) -> dict:
        return dict(max_depth=self.flat.max_depth,
                    snapshot_keys=int(self.flat.n_pairs),
                    table_bytes=self._K.table_bytes(self.arrs),
                    kernel_eligible=(self._K.table_bytes(self.arrs)
                                     <= self.cfg.vmem_budget_bytes),
                    kernel_routes=dict(self.routes),
                    recheck_patched_lanes=self.recheck_patched_lanes,
                    device_bytes=self.snap.nbytes)


# ---------------------------------------------------------------------------
# ShardedEngine
# ---------------------------------------------------------------------------


class ShardedEngine(EngineTelemetryBase):
    """Mesh engine: quantile range partitioning, per-shard tombstone
    overlays, collective lookups (gather or a2a) with in-shard overlay
    resolution, and single-shard merges + republish.  Query batches are
    padded to a shard multiple with +inf (guaranteed misses) and unpadded
    on the way out, so callers never see the mesh shape."""

    name = "sharded"

    def __init__(self, keys: np.ndarray, vals: np.ndarray, cfg: IndexConfig):
        _require_x64(cfg)
        self.cfg = cfg
        self.telemetry = Telemetry(enabled=cfg.telemetry)
        _reject_background(cfg, self.name)
        n = cfg.n_shards or len(jax.devices())
        # every shard's bulk_load needs >= 2 keys, and the mesh cannot span
        # more devices than exist; a tiny index (e.g. a freshly warmed
        # session table) clamps to fewer shards rather than crashing — it
        # grows back onto more shards at the next build
        n = max(1, min(n, len(keys) // 2, len(jax.devices())))
        self.sd = build_sharded(keys, vals, n_shards=n,
                                overlay_cap=cfg.overlay_cap, keep_host=True,
                                **cfg.bulk_load_kw())
        self.mesh = jax.make_mesh((n,), (cfg.mesh_axis,))
        m = cfg.maintenance
        self._flatteners = ([IncrementalFlattener() for _ in range(n)]
                            if m is not None and m.incremental else None)
        self._accounting = ([LeafAccounting(m) for _ in range(n)]
                            if m is not None and (m.retrain or m.recluster)
                            else None)
        self.n_flattens = n                      # build flattened every shard
        self.n_full_flattens = n
        self.n_incremental_flattens = 0
        self.n_merges = 0
        self.n_retrains = 0
        self.n_reclusters = 0
        self.last_dirty_frac = 1.0
        self.n_publishes = 1
        self._timings: list[dict] = []
        self._writes_since_publish = 0
        self._writes_since_pressure = 0
        self.arrs = to_mesh(self.sd, self.mesh, axis=cfg.mesh_axis,
                            dtype=cfg.resolved_dtype)

    def _pad(self, x) -> tuple[np.ndarray, int]:
        x = np.atleast_1d(np.asarray(x, np.float64))
        pad = (-len(x)) % self.sd.n_shards
        if pad:
            x = np.concatenate([x, np.full(pad, np.inf)])
        return x, len(x) - pad

    # -- reads --------------------------------------------------------------

    def lookup(self, queries):
        q, n = self._pad(queries)
        qd = jnp.asarray(q, self.cfg.resolved_dtype)
        ova = combined_overlay_arrays(self.sd, self.cfg.resolved_dtype)
        out = sharded_lookup(self.mesh, self.arrs, qd, self.sd.max_depth,
                             axis=self.cfg.mesh_axis,
                             strategy=self.cfg.lookup_strategy, overlay=ova,
                             has_dense=self.sd.has_dense)
        if (self.cfg.lookup_strategy == "a2a"
                and int(np.asarray(out[2]).sum()) > 0):
            # a2a buckets are capacity-bounded; overflowed lanes come back
            # found=False.  The facade's contract is exact results, so a
            # skewed batch that overflows re-resolves on the (always-exact)
            # gather path instead of silently reporting misses.
            out = sharded_lookup(self.mesh, self.arrs, qd,
                                 self.sd.max_depth, axis=self.cfg.mesh_axis,
                                 strategy="gather", overlay=ova,
                                 has_dense=self.sd.has_dense)
        v, f = out[0], out[1]
        return (np.asarray(v, np.int64)[:n], np.asarray(f, bool)[:n])

    def range(self, lo, hi, max_hits):
        lo_p, n = self._pad(lo)
        hi_p, _ = self._pad(hi)
        dt = self.cfg.resolved_dtype

        def device_range(_lo, _hi, fetch):
            # the collective needs the shard-multiple padded batch; results
            # are sliced back to the caller's n queries
            ks, vs, cnt = sharded_range_query(
                self.mesh, self.arrs, jnp.asarray(lo_p, dt),
                jnp.asarray(hi_p, dt), max_hits=fetch,
                axis=self.cfg.mesh_axis)
            return (np.asarray(ks)[:n], np.asarray(vs)[:n],
                    np.asarray(cnt)[:n])

        return _overlay_exact_range(self._overlay_entries(), lo_p[:n],
                                    hi_p[:n], max_hits, device_range)

    def _overlay_entries(self):
        """Combined overlay entries, globally sorted (disjoint shard
        ranges => shard-order concatenation IS key order)."""
        parts = [ov.entries() for ov in self.sd.overlays]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                np.concatenate([p[2] for p in parts]))

    def get(self, key: float):
        k = float(key)
        r = int(shard_of(self.sd, np.array([k]))[0])
        state, v = self.sd.overlays[r].get(k)
        if state == 0:
            return v
        if state == 1:
            return None
        return self.sd.dilis[r].search(k)

    # -- writes -------------------------------------------------------------

    def upsert(self, keys, vals):
        sharded_upsert(self.sd, keys, vals)
        self._note_writes(len(np.atleast_1d(keys)))

    def delete(self, keys):
        sharded_delete(self.sd, keys)
        self._note_writes(len(np.atleast_1d(keys)))

    def _note_writes(self, n: int):
        p = self.cfg.merge
        self._writes_since_publish += n
        self._writes_since_pressure += n
        trigger = (self._writes_since_publish >= p.max_writes
                   or any(ov.full_fraction >= p.max_fill
                          for ov in self.sd.overlays))
        if not trigger and self._writes_since_pressure >= p.pressure_check_every:
            self._writes_since_pressure = 0
            trigger = any(
                ov.count and (adjust_pressure(d, ov, p.pressure_min_pending)
                              > p.pressure_lambda)
                for d, ov in zip(self.sd.dilis, self.sd.overlays))
        if trigger:
            self.flush()

    def _fold_shard(self, r: int, dili, ov) -> None:
        # always the sharded_merge fold hook, so the per-shard fold (and
        # any retrains) land as per-shard merge.fold/retrain spans
        if self._accounting is None:
            with self.telemetry.span("merge.fold", shard=r):
                fold_overlay(dili, ov)
            return
        acct = self._accounting[r]
        with self.telemetry.span("merge.fold", shard=r):
            fold_with_accounting(dili, ov, acct)
        with self.telemetry.span("merge.retrain", shard=r):
            self.n_retrains += run_retrains(dili, acct)
        fl = self._flatteners[r] if self._flatteners is not None else None
        with self.telemetry.span("merge.recluster", shard=r):
            n = run_reclusters(dili, acct, fl)
        if n:
            self.n_reclusters += n
            if self.telemetry.enabled:
                self.telemetry.metrics.count("maint.reclusters", n)

    def _flatten_shard(self, r: int, dili):
        with self.telemetry.span("merge.flatten", shard=r):
            if self._flatteners is None:
                flat = flatten(dili)
                dili.take_dirty()   # drain (a full flatten supersedes it)
                self.n_full_flattens += 1
                return flat
            fl = self._flatteners[r]
            flat = fl.flatten(dili, dili.take_dirty())
        if fl.last_incremental:
            self.n_incremental_flattens += 1
        else:
            self.n_full_flattens += 1
        return flat

    def flush(self):
        """Fold every shard with pending writes and republish the mesh
        copy.  (A policy trigger folds all pending shards too — the merge
        itself is still per-shard row rewrites, no global rebuild.)"""
        t0 = time.perf_counter()
        merged = sharded_merge(self.sd, max_fill=0.0,
                               fold_fn=self._fold_shard,
                               flatten_fn=self._flatten_shard)
        if merged:
            incremental = False
            if self._flatteners is not None:
                fls = [self._flatteners[r] for r in merged]
                self.last_dirty_frac = (
                    sum(f.last_dirty_rows for f in fls)
                    / max(sum(f.last_total_rows for f in fls), 1))
                # honest labeling: a flush is incremental only if every
                # merged shard actually spliced (cold caches full-flatten)
                incremental = all(f.last_incremental for f in fls)
            total_slots = sum(f.n_slots for f in self.sd.flats)
            self.telemetry.sample_publish(
                n_segments=sum(f.n_segments for f in self.sd.flats),
                dirty_rows=(sum(f.last_dirty_rows
                                for f in self._flatteners)
                            if self._flatteners is not None
                            else total_slots),
                total_rows=(sum(f.last_total_rows
                                for f in self._flatteners)
                            if self._flatteners is not None
                            else total_slots))
            merge_s = time.perf_counter() - t0
            self.n_merges += 1
            self.n_flattens += len(merged)
            self._writes_since_publish = 0
            self._writes_since_pressure = 0
            t0 = time.perf_counter()
            with self.telemetry.span("merge.publish", shards=len(merged)):
                self.arrs = to_mesh(self.sd, self.mesh,
                                    axis=self.cfg.mesh_axis,
                                    dtype=self.cfg.resolved_dtype)
                jax.block_until_ready(list(self.arrs.values()))
            self.n_publishes += 1
            self._timings.append(dict(
                merge_s=merge_s, publish_s=time.perf_counter() - t0,
                incremental=incremental,
                dirty_frac=self.last_dirty_frac))
            self._notify_publish()

    # -- introspection ------------------------------------------------------

    def _n_forced_full_flattens(self) -> int:
        if self._flatteners is None:
            return 0
        return sum(fl.n_fallback_full for fl in self._flatteners)

    @property
    def n_wal_shards(self) -> int:
        return self.sd.n_shards

    def shard_ids(self, keys: np.ndarray) -> np.ndarray:
        return np.asarray(
            shard_of(self.sd, np.atleast_1d(np.asarray(keys, np.float64))),
            np.int64)

    def items(self):
        snap_k = np.concatenate([f.pair_key for f in self.sd.flats])
        snap_v = np.concatenate([f.pair_val for f in self.sd.flats])
        ok, ovv, ott = self._overlay_entries()
        return _merged_items(snap_k, snap_v, ok, ovv, ott)

    @property
    def host(self):
        return self.sd.dilis

    @property
    def epoch(self) -> int:
        # publish-count semantics, like the other engines (the local
        # engine's SnapshotStore and the pallas engine both count device
        # republishes, so a fresh build is epoch 1 and every effective
        # flush bumps it); `sd.epoch` (merge count) stays internal
        return self.n_publishes

    def _timing_rows(self) -> list[dict]:
        return list(self._timings)

    def _stats_overlays(self):
        return self.sd.overlays

    def _inspect_flats(self) -> list:
        return list(self.sd.flats)

    def _inspect_flatteners(self) -> list:
        return list(self._flatteners or ())

    def _inspect_accounts(self) -> list:
        return list(self._accounting or ())

    def _stats_extra(self) -> dict:
        return dict(max_depth=self.sd.max_depth,
                    n_shards=self.sd.n_shards,
                    snapshot_keys=sum(int(f.n_pairs) for f in self.sd.flats),
                    per_shard_pending=[ov.count for ov in self.sd.overlays],
                    n_publishes=self.n_publishes,
                    device_ids=[int(d.id) for d in self.mesh.devices.flat],
                    device_bytes=sum(int(np.prod(v.shape)) * v.dtype.itemsize
                                     for v in self.arrs.values()))


ENGINE_CLASSES = {
    "local": LocalEngine,
    "pallas": PallasEngine,
    "sharded": ShardedEngine,
}
