"""`LearnedIndex`: one index object, many engines.

The paper presents DILI as a single index with one contract — build,
search, range, insert, delete (Alg. 1/4/6/7/8).  This facade restores that
contract over the repo's three execution substrates: pick an engine in
`IndexConfig`, and every workload (serving session tables, record stores,
benchmarks, examples) composes with it unchanged.

    from repro.api import IndexConfig, LearnedIndex

    ix = LearnedIndex.build(keys, vals, config=IndexConfig(engine="local"))
    vals, found = ix.lookup(queries)
    ks, vs, cnt = ix.range(lo, hi, max_hits=64)
    ix.upsert(new_keys, new_vals)      # visible immediately (overlay)
    ix.delete(dead_keys)               # visible immediately (tombstones)
    ix.flush()                         # fold + republish (Alg. 7/8)
    ix.save("index.npz"); ix2 = LearnedIndex.load("index.npz")
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import replace

import numpy as np

from ..durability.wal import OP_DELETE, OP_UPSERT
from .config import IndexConfig
from .engines import ENGINE_CLASSES, Engine


class LearnedIndex:
    """Engine-agnostic DILI facade.  All inputs/outputs are host numpy;
    device placement, sharding, kernel dispatch, overlay/merge scheduling,
    and depth threading are the engine's business.

    Threading contract (DESIGN.md sections 8/15):

      * ONE logical writer: the engines' overlay/merge machinery assumes
        a single mutating caller.  The facade enforces it — `upsert`,
        `delete`, and `flush` serialize on an internal RLock, so
        accidental concurrent writers are safe (they queue) but the
        intended deployment is a single writer thread (the serving
        front-end's batcher is exactly that).  The lock also keeps the
        WAL-append -> engine-apply pair atomic, preserving the
        durability ordering contract under contention.
      * Reads (`lookup`/`range`/`get`/`items`) are lock-free: they
        resolve against the current published snapshot + a functional
        overlay reference, which engine publication swaps atomically.
      * `stats()` and `metrics()` are safe to sample from ANY thread
        while the writer runs — they read counters and copied dicts,
        never partial engine state (hammered by tests/test_serve.py).
    """

    def __init__(self, engine: Engine, config: IndexConfig):
        self._engine = engine
        self.config = config
        self._dur = None        # DurabilityManager when config.durability
        self._write_lock = threading.RLock()

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, keys, vals=None, config: IndexConfig | None = None,
              **overrides) -> "LearnedIndex":
        """Bulk-load (Alg. 4) through the configured engine.  `overrides`
        are `IndexConfig` field replacements, e.g. `engine="pallas"`.

        With `config.durability` set, a fresh WAL + base checkpoint are
        armed under `durability.dir` (any previous durability state there
        is superseded — use `LearnedIndex.recover` to resurrect it
        instead of rebuilding)."""
        cfg = config or IndexConfig()
        if overrides:
            cfg = replace(cfg, **overrides)
        keys = np.atleast_1d(np.asarray(keys, np.float64))
        if vals is None:
            vals = np.arange(len(keys), dtype=np.int64)
        vals = np.atleast_1d(np.asarray(vals, np.int64))
        if len(keys) != len(vals):
            raise ValueError(f"{len(keys)} keys vs {len(vals)} vals")
        if len(keys) == 0:
            raise ValueError("cannot build an empty index")
        if not np.isfinite(keys).all():
            raise ValueError("keys must be finite")
        # the engines' bulk loaders require sorted unique keys; normalize at
        # the public boundary (duplicates collapse last-write-wins, matching
        # upsert semantics) so every engine sees the same contract
        order = np.argsort(keys, kind="stable")
        keys, vals = keys[order], vals[order]
        keep = np.ones(len(keys), bool)
        keep[:-1] = keys[:-1] != keys[1:]
        keys, vals = keys[keep], vals[keep]
        ix = cls(ENGINE_CLASSES[cfg.engine](keys, vals, cfg), cfg)
        if cfg.durability is not None:
            ix._attach_durability(fresh=True)
        return ix

    def _attach_durability(self, *, fresh: bool,
                           resume_lsns: dict | None = None,
                           start_step: int = 0) -> None:
        """Arm the WAL + checkpoint subsystem for this index (DESIGN.md
        section 14) and hook merge publishes to checkpointing."""
        from ..durability.manager import DurabilityManager
        self._dur = DurabilityManager.attach(
            self.config.durability, self, fresh=fresh,
            resume_lsns=resume_lsns, start_step=start_step)
        self._engine.set_on_publish(self._dur.on_merge_publish)

    @classmethod
    def recover(cls, dur_dir: str, config: IndexConfig | None = None,
                engine: str | None = None) -> "LearnedIndex":
        """Rebuild from the durability directory after a crash: newest
        valid checkpoint + WAL tail replay (`repro.durability.recover`)."""
        from ..durability.recovery import recover as _recover
        return _recover(dur_dir, config=config, engine=engine)

    # -- reads ---------------------------------------------------------------

    def _pad_batch(self, n: int) -> int:
        """pow2 lane count for a batch of n queries (0 = don't pad).

        With `config.pad` the facade pow2-pads query batches exactly like
        the engines pow2-pad their tables, and for the same reason: a
        compiled executable is keyed by shape, so serving a stream of
        arbitrary batch lengths would re-trace per new length (the retrace
        watchdog caught the runner's mixed workloads doing exactly this).
        Padded lanes repeat a real query and are sliced off the result —
        at most 2x lane work for a bounded, log-sized executable set."""
        if not self.config.pad or n == 0:
            return 0
        return 1 << max(6, (n - 1).bit_length())     # >= 64 lanes

    def lookup(self, queries) -> tuple[np.ndarray, np.ndarray]:
        """Batched point lookups -> (vals int64, found bool); vals only
        valid where found."""
        tel = self._engine.telemetry
        with tel.span("engine.prep"):
            q = np.atleast_1d(np.asarray(queries, np.float64))
            if not np.isfinite(q).all():
                # engines use +/-inf internally as padding/boundary
                # sentinels; a non-finite query would match them
                # (engine-dependently)
                raise ValueError("queries must be finite")
            n = len(q)
            lanes = self._pad_batch(n)
            if lanes > n:
                q = np.concatenate([q, np.full(lanes - n, q[0])])
        if tel.enabled:
            t0 = time.perf_counter()
            v, f = self._engine.lookup(q)
            dur = time.perf_counter() - t0
            tel.record_op("lookup", dur, n)
            if tel.trace.enabled:
                tel.trace.add("op.lookup", t0=t0, dur_s=dur,
                              track="facade", n_ops=n)
        else:
            tel.count_ops(n)
            v, f = self._engine.lookup(q)
        return (np.asarray(v, np.int64)[:n],
                np.asarray(f, bool)[:n])

    def range(self, lo, hi,
              max_hits: int | None = None
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """For each [lo, hi): the first `max_hits` live pairs ascending —
        (keys [Q,H] +inf-padded, vals [Q,H] -1-padded, counts [Q]
        saturating at `max_hits`).  Overlay-exact: pending upserts appear,
        pending deletes are hidden."""
        tel = self._engine.telemetry
        with tel.span("engine.prep"):
            lo = np.atleast_1d(np.asarray(lo, np.float64))
            hi = np.atleast_1d(np.asarray(hi, np.float64))
            if lo.shape != hi.shape:
                raise ValueError(f"lo {lo.shape} vs hi {hi.shape}")
            if not (np.isfinite(lo).all() and np.isfinite(hi).all()):
                raise ValueError("range bounds must be finite")
            if max_hits is None:
                max_hits = self.config.max_hits
            if max_hits < 1:
                raise ValueError(f"max_hits must be >= 1, got {max_hits}")
            n = len(lo)
            lanes = self._pad_batch(n)
            if lanes > n:
                lo = np.concatenate([lo, np.full(lanes - n, lo[0])])
                hi = np.concatenate([hi, np.full(lanes - n, hi[0])])
        if tel.enabled:
            t0 = time.perf_counter()
            ks, vs, cnt = self._engine.range(lo, hi, max_hits)
            dur = time.perf_counter() - t0
            tel.record_op("range", dur, n)
            if tel.trace.enabled:
                tel.trace.add("op.range", t0=t0, dur_s=dur,
                              track="facade", n_ops=n)
        else:
            tel.count_ops(n)
            ks, vs, cnt = self._engine.range(lo, hi, max_hits)
        if lanes > n:
            ks, vs, cnt = ks[:n], vs[:n], cnt[:n]
        return ks, vs, cnt

    def get(self, key: float) -> int | None:
        """Host-side exact point read (overlay state wins)."""
        return self._engine.get(float(key))

    # -- writes --------------------------------------------------------------

    def upsert(self, keys, vals) -> None:
        """Insert-or-update (Alg. 7 at merge time); visible immediately."""
        keys = np.atleast_1d(np.asarray(keys, np.float64))
        vals = np.atleast_1d(np.asarray(vals, np.int64))
        if len(keys) != len(vals):
            raise ValueError(f"{len(keys)} keys vs {len(vals)} vals")
        if not np.isfinite(keys).all():
            raise ValueError("keys must be finite")
        tel = self._engine.telemetry
        with self._write_lock:
            if tel.enabled:
                t0 = time.perf_counter()
                self._log_write(OP_UPSERT, keys, vals)
                self._engine.upsert(keys, vals)
                dur = time.perf_counter() - t0
                tel.record_op("upsert", dur, len(keys))
                if tel.trace.enabled:
                    tel.trace.add("op.upsert", t0=t0, dur_s=dur,
                                  track="facade", n_ops=len(keys))
            else:
                tel.count_ops(len(keys))
                self._log_write(OP_UPSERT, keys, vals)
                self._engine.upsert(keys, vals)

    def delete(self, keys) -> None:
        """Delete (Alg. 8 at merge time); visible immediately."""
        keys = np.atleast_1d(np.asarray(keys, np.float64))
        if not np.isfinite(keys).all():
            raise ValueError("keys must be finite")
        tel = self._engine.telemetry
        with self._write_lock:
            if tel.enabled:
                t0 = time.perf_counter()
                self._log_write(OP_DELETE, keys, None)
                self._engine.delete(keys)
                dur = time.perf_counter() - t0
                tel.record_op("delete", dur, len(keys))
                if tel.trace.enabled:
                    tel.trace.add("op.delete", t0=t0, dur_s=dur,
                                  track="facade", n_ops=len(keys))
            else:
                tel.count_ops(len(keys))
                self._log_write(OP_DELETE, keys, None)
                self._engine.delete(keys)

    def _log_write(self, op: int, keys: np.ndarray,
                   vals: np.ndarray | None) -> None:
        """WAL-before-apply: persist the batch before the engine (and
        thus the caller) sees it as accepted.  A crash between the append
        and the in-memory apply replays a write the engine never served —
        upsert/delete replay is idempotent, so that is safe; the reverse
        order would acknowledge writes a crash could lose."""
        if self._dur is not None:
            tr = self._engine.telemetry.trace
            if tr.enabled:
                t0 = time.perf_counter()
                self._dur.log(op, keys, vals, epoch=self._engine.epoch,
                              shard_ids=self._engine.shard_ids(keys))
                tr.add("wal.append", t0=t0,
                       dur_s=time.perf_counter() - t0, track="wal",
                       n_ops=len(keys))
            else:
                self._dur.log(op, keys, vals, epoch=self._engine.epoch,
                              shard_ids=self._engine.shard_ids(keys))

    def flush(self) -> dict:
        """Fold every pending write through the host tree and republish;
        returns `stats()` afterwards.  With background maintenance this is
        the synchronous barrier (drains the worker first)."""
        tel = self._engine.telemetry
        with self._write_lock:
            if tel.enabled:
                t0 = time.perf_counter()
                self._engine.flush()
                tel.record_op("flush", time.perf_counter() - t0)
            else:
                tel.count_ops(1)
                self._engine.flush()
            if self._dur is not None:
                self._dur.sync()  # flush doubles as the durability barrier
        return self.stats()

    def close(self) -> None:
        """Release engine resources (stops the background maintenance
        worker when one is running).  Pending writes stay readable but are
        no longer folded; idempotent.  With durability armed, the WAL gets
        a final fsync AFTER the engine drains (a draining background merge
        may still publish a checkpoint through the manager)."""
        close = getattr(self._engine, "close", None)
        if close is not None:
            close()
        if self._dur is not None:
            self._dur.close()

    def abandon(self) -> None:
        """Crash simulation (tests/benchmarks): drop the index WITHOUT the
        final WAL fsync, as a SIGKILL would.  The engine's background
        worker is still stopped so the process can exit."""
        if self._dur is not None:
            self._dur.abandon()  # first: late publishes must no-op
        close = getattr(self._engine, "close", None)
        if close is not None:
            close()

    def __enter__(self) -> "LearnedIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- introspection -------------------------------------------------------

    def items(self) -> tuple[np.ndarray, np.ndarray]:
        """The full live (keys, vals) content, key-sorted (O(n))."""
        return self._engine.items()

    def stats(self) -> dict:
        return self._engine.stats()

    def maint_timings(self) -> list[dict]:
        """Per-merge wall times (merge_s fold+retrain+flatten, publish_s
        upload+flip, incremental, dirty_frac) — benchmark material."""
        return self._engine.maint_timings()

    def metrics(self) -> dict:
        """The stable JSON-able telemetry snapshot (DESIGN.md section 13):
        per-op latency histograms, merge-pipeline span summaries, and the
        retrace watchdog report.  Schema is identical across engines; with
        `config.telemetry` off, histograms/spans are zero-count but op and
        retrace accounting are still live."""
        return self._engine.metrics()

    def inspect(self) -> dict:
        """The `dili.inspect/1` index-health document (DESIGN.md section
        13): depth/fanout histograms, leaf fill, per-leaf model
        prediction-error distribution, segment dirty-fraction breakdown,
        heat accounting, overlay + WAL footprint.  Computed from host-side
        columns (no device sync); the key tree is identical across
        engines.  Safe to call on a serving index."""
        doc = self._engine.inspect()
        if self._dur is not None:
            doc["wal"] = dict(doc["wal"], **self._wal_inspect())
        return doc

    def _wal_inspect(self) -> dict:
        """On-disk durability footprint (armed indexes only)."""
        def du(d):
            # recursive: WAL segments live under shard_NNNNN/ subdirs,
            # checkpoints under step_NNNNNNNN/ subdirs
            b = n = 0
            for root, _dirs, files in os.walk(d):
                for f in files:
                    try:
                        b += os.path.getsize(os.path.join(root, f))
                        n += 1
                    except OSError:
                        pass
            return b, n
        wal_b, wal_n = du(str(self._dur.wal_dir))
        ck_b, ck_n = du(str(self._dur.ckpt_dir))
        return dict(armed=True, n_shards=len(self._dur.writers),
                    wal_bytes=int(wal_b), n_wal_files=int(wal_n),
                    ckpt_bytes=int(ck_b), n_ckpt_files=int(ck_n))

    # -- causal tracing -------------------------------------------------------

    def start_trace(self) -> None:
        """Arm end-to-end causal tracing (requires `config.telemetry`):
        facade ops, WAL appends, serve spans, and merge/recovery spans are
        collected into a bounded ring, linked to the client requests that
        caused them.  Export with `dump_trace`."""
        self._engine.telemetry.start_trace()

    def stop_trace(self) -> None:
        self._engine.telemetry.stop_trace()

    def dump_trace(self, path: str) -> str:
        """Write the collected trace as Chrome-trace-event JSON (open at
        https://ui.perfetto.dev).  Returns `path`."""
        return self._engine.telemetry.trace.dump(
            path, process_name=f"dili:{self.engine}")

    @property
    def telemetry(self):
        """The engine's `repro.obs.Telemetry` bundle (e.g. for
        `mark_warm()` after a benchmark warmup phase)."""
        return self._engine.telemetry

    @property
    def engine(self) -> str:
        return self._engine.name

    @property
    def epoch(self) -> int:
        return self._engine.epoch

    @property
    def n_flattens(self) -> int:
        return self._engine.n_flattens

    @property
    def n_merges(self) -> int:
        return self._engine.n_merges

    @property
    def host(self):
        """The mutable host writer (engine-specific; introspection only)."""
        return self._engine.host

    @property
    def snapshot(self):
        """The engine's current `DeviceSnapshot` for low-level `core.search`
        composition (e.g. `with_stats` probe counting), or None when the
        engine has no single-device snapshot (sharded)."""
        return getattr(self._engine, "snapshot", None)

    # -- persistence ---------------------------------------------------------

    @staticmethod
    def _npz_path(path: str) -> str:
        # np.savez appends .npz to bare paths; normalize on both sides so
        # save(p) -> load(p) always round-trips
        return path if path.endswith(".npz") else path + ".npz"

    def save(self, path: str) -> None:
        """Persist the logical content (live keys/vals incl. pending
        writes) + config.  Load rebuilds the tree — snapshots are derived
        state, and a rebuild re-optimizes the layout for the merged
        distribution.  `config.bulk_kw` must be JSON-serializable.

        The write is atomic (tmp file + `os.replace`): a crash mid-save
        leaves either the previous file or the new one, never a torn
        npz."""
        keys, vals = self.items()
        dst = self._npz_path(path)
        tmp = dst + ".tmp"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, keys=keys, vals=vals,
                         config=np.frombuffer(
                             json.dumps(self.config.to_json_dict()).encode(),
                             dtype=np.uint8))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, dst)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    @classmethod
    def load(cls, path: str,
             config: IndexConfig | None = None) -> "LearnedIndex":
        """Rebuild from `save()` output; `config` overrides the saved one
        (e.g. load a locally-built index onto the sharded engine)."""
        with np.load(cls._npz_path(path)) as z:
            keys, vals = z["keys"], z["vals"]
            saved = json.loads(bytes(z["config"].tobytes()).decode())
        return cls.build(keys, vals,
                         config=config or IndexConfig.from_json_dict(saved))
