"""Durability subsystem (DESIGN.md section 14): WAL codec + torn-tail
truncation, checkpoint corruption fallback, in-process crash/recover
round trips, the subprocess crash-injection matrix (tests/crashkit.py),
and the hardened-maintenance satellites (bounded merge retries with
degrade-to-sync, merge.failed/maint.errors observability)."""
import os

import numpy as np
import pytest

import crashkit
from repro.api import (DurabilityConfig, IndexConfig, LearnedIndex,
                       MaintenanceConfig, manual_merge_policy)
from repro.durability import wal
from repro.durability import checkpoint as dckpt
from repro.workloads.generator import PRESETS, generate_stream
from repro.workloads.oracle import SortedOracle
from repro.workloads.runner import WorkloadRunner
from tests.conftest import ycsb_keys

ENGINES = ("local", "pallas", "sharded")


def _dur_cfg(tmp_path, engine="local", fsync="always", **kw):
    return IndexConfig(engine=engine, merge=manual_merge_policy(),
                       overlay_cap=128,
                       durability=DurabilityConfig(
                           dir=str(tmp_path / "dur"), fsync=fsync, **kw))


# ---------------------------------------------------------------------------
# WAL unit tests
# ---------------------------------------------------------------------------


def test_wal_record_round_trip(tmp_path):
    d = str(tmp_path / "w")
    w = wal.WalWriter(d, fsync="always")
    k1 = np.array([1.5, 2.5, 99.0])
    v1 = np.array([10, 20, 30], np.int64)
    assert w.append(wal.OP_UPSERT, k1, v1, epoch=3) == 0
    assert w.append(wal.OP_DELETE, np.array([2.5]), None, epoch=3) == 1
    w.close()
    recs = wal.read_records(d)
    assert [r["lsn"] for r in recs] == [0, 1]
    assert recs[0]["op"] == wal.OP_UPSERT and recs[0]["epoch"] == 3
    np.testing.assert_array_equal(recs[0]["keys"], k1)
    np.testing.assert_array_equal(recs[0]["vals"], v1)
    assert recs[1]["op"] == wal.OP_DELETE and recs[1]["vals"] is None
    np.testing.assert_array_equal(recs[1]["keys"], [2.5])


def test_wal_torn_tail_truncates_at_first_bad_crc(tmp_path):
    d = str(tmp_path / "w")
    w = wal.WalWriter(d, fsync="always")
    for i in range(4):
        w.append(wal.OP_UPSERT, np.array([float(i)]),
                 np.array([i], np.int64), epoch=1)
    w.close()
    (_, path), = wal.list_segments(d)
    full = os.path.getsize(path)
    # a half-written trailing record: everything before it must survive
    with open(path, "ab") as f:
        f.write(wal.encode_record(4, 1, wal.OP_UPSERT, np.array([9.0]),
                                  np.array([9], np.int64))[:11])
    assert [r["lsn"] for r in wal.read_records(d)] == [0, 1, 2, 3]
    # flip one payload byte mid-file: records BEFORE it survive, the
    # corrupt one and everything after are dropped (CRC catches it)
    with open(path, "r+b") as f:
        f.truncate(full)
        f.seek(full // 2)
        b = f.read(1)
        f.seek(full // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    lsns = [r["lsn"] for r in wal.read_records(d)]
    assert lsns == list(range(len(lsns))) and len(lsns) < 4


def test_wal_rotate_purge_and_resume(tmp_path):
    d = str(tmp_path / "w")
    w = wal.WalWriter(d, fsync="always")
    for i in range(3):
        w.append(wal.OP_DELETE, np.array([float(i)]), None, epoch=1)
    w.rotate()                               # seg[0..3) closed, seg[3..) live
    w.append(wal.OP_DELETE, np.array([7.0]), None, epoch=1)
    assert len(wal.list_segments(d)) == 2
    assert w.purge_upto(2) == 0              # watermark inside the closed seg
    assert w.purge_upto(3) == 1              # whole closed range checkpointed
    assert [r["lsn"] for r in wal.read_records(d, from_lsn=3)] == [3]
    w.close()
    # a resumed writer continues the lsn sequence in the same directory
    w2 = wal.WalWriter(d, fsync="always", start_lsn=wal.end_lsn(d))
    assert w2.append(wal.OP_DELETE, np.array([8.0]), None, epoch=2) == 4
    w2.close()
    assert [r["lsn"] for r in wal.read_records(d, from_lsn=3)] == [3, 4]


# ---------------------------------------------------------------------------
# durability checkpoint fallback
# ---------------------------------------------------------------------------


def _write_ckpt(d, step, n):
    keys = np.arange(n, dtype=np.float64)
    return dckpt.write_checkpoint(
        str(d), step, keys, (keys * 2).astype(np.int64),
        epoch=step, wal_lsns={0: step * 10}, keep=3)


def test_checkpoint_corrupt_newest_falls_back(tmp_path):
    d = tmp_path / "ckpt"
    _write_ckpt(d, 1, 50)
    p2 = _write_ckpt(d, 2, 60)
    # corrupt the newest checkpoint's array payload
    npz = os.path.join(p2, "state.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(blob))
    name, manifest, keys, _ = next(dckpt.iter_checkpoints(str(d)))
    assert manifest["step"] == 1 and len(keys) == 50
    # with the newest manifest gone instead, same fallback
    _write_ckpt(d, 3, 70)
    os.remove(os.path.join(str(d), dckpt.ftck.step_name(3),
                           "manifest.json"))
    name, manifest, keys, _ = next(dckpt.iter_checkpoints(str(d)))
    assert manifest["step"] == 1 and len(keys) == 50


# ---------------------------------------------------------------------------
# in-process build -> crash -> recover round trips
# ---------------------------------------------------------------------------


# key shapes and the engines that serve them
RECOVERY_SHAPES = {"int": ENGINES, "ycsb_hashed": ("local", "sharded"),
                   "ycsb_ordered": ENGINES}


@pytest.mark.parametrize("engine,shape", [
    pytest.param(e, shape, id=e if shape == "int" else f"{e}-{shape}")
    for shape, engines in RECOVERY_SHAPES.items() for e in engines])
def test_abandon_recover_round_trip(tmp_path, engine, shape):
    """The acknowledged write stream survives an un-fsynced abandon on
    every engine: checkpointed prefix + WAL tail == oracle.  The YCSB key
    sets are the served cells' (`bench/keys.py`): fnv64 record hashes,
    whose inserts are the next records' hashes (`keys + 0.5` would not
    move them), and dense record numbers, whose inserts are tail inserts."""
    rng = np.random.default_rng(5)
    if shape == "int":
        keys = np.unique(rng.integers(0, 1 << 20, 900)).astype(np.float64)
        up_k = keys[:40] + 0.5
    else:
        keys, up_k = ycsb_keys(shape.removeprefix("ycsb_"), 900, 40)
    vals = rng.integers(0, 1 << 30, len(keys)).astype(np.int64)
    oracle = SortedOracle(keys, vals)
    ix = LearnedIndex.build(keys, vals, config=_dur_cfg(tmp_path, engine))
    up_v = np.arange(40, dtype=np.int64)
    ix.upsert(up_k, up_v)
    oracle.upsert(up_k, up_v)
    ix.flush()                               # checkpointed prefix
    ix.delete(keys[100:120])
    oracle.delete(keys[100:120])             # un-flushed WAL tail
    ix.abandon()

    rx = LearnedIndex.recover(str(tmp_path / "dur"))
    try:
        k, v = rx.items()
        wk, wv = oracle.items()
        np.testing.assert_array_equal(k, wk)
        np.testing.assert_array_equal(v, wv)
        lv, lf = rx.lookup(wk)
        assert lf.all()
        np.testing.assert_array_equal(lv, wv)
        assert rx.engine == engine
        m = rx.metrics()
        assert m["counters"]["recovery.count"] == 1
        assert m["counters"]["recovery.replayed_records"] == 1
        # recovery spans are recorded even with telemetry disabled
        for s in ("recovery.load", "recovery.replay", "recovery.publish"):
            assert m["spans"][s]["count"] == 1, s
        # the recovered index is a live durable writer
        rx.upsert([3.25], [777])
        rx.flush()
    finally:
        rx.close()
    rz = LearnedIndex.recover(str(tmp_path / "dur"))
    try:
        assert rz.get(3.25) == 777
    finally:
        rz.close()


def test_recover_without_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        LearnedIndex.recover(str(tmp_path / "nothing"))


def test_clean_close_then_recover(tmp_path):
    ix = LearnedIndex.build(np.arange(64, dtype=np.float64),
                            config=_dur_cfg(tmp_path, fsync="interval"))
    ix.upsert([7.5], [70])
    ix.close()                               # final fsync, clean shutdown
    rx = LearnedIndex.recover(str(tmp_path / "dur"))
    try:
        assert rx.get(7.5) == 70
    finally:
        rx.close()


def test_wal_truncation_after_checkpoints(tmp_path):
    """Checkpoints advance the watermark and old segments are purged —
    but only past the OLDEST retained checkpoint, so the fallback path
    always has enough tail."""
    cfg = _dur_cfg(tmp_path, keep_checkpoints=2)
    ix = LearnedIndex.build(np.arange(256, dtype=np.float64), config=cfg)
    dur = ix._dur
    for i in range(5):
        ix.upsert(np.arange(8, dtype=np.float64) + 1000 + 16 * i,
                  np.arange(8, dtype=np.int64))
        ix.flush()                           # merge publish -> checkpoint
    manifests = dckpt.retained_manifests(os.path.join(cfg.durability.dir,
                                                      "ckpt"))
    assert len(manifests) == 2               # keep_checkpoints enforced
    oldest = min(int(m["wal_lsns"]["0"]) for m in manifests)
    segs = wal.list_segments(os.path.join(cfg.durability.dir, "wal",
                                          "shard_00000"))
    # every surviving segment still covers the oldest retained watermark
    assert all(start >= oldest or i + 1 == len(segs)
               or segs[i + 1][0] > oldest for i, (start, _) in
               enumerate(segs))
    assert dur is ix._dur
    ix.close()


def test_config_round_trips_durability(tmp_path):
    cfg = _dur_cfg(tmp_path, fsync="interval")
    back = IndexConfig.from_json_dict(cfg.to_json_dict())
    assert back.durability == cfg.durability
    assert IndexConfig.from_json_dict(
        IndexConfig().to_json_dict()).durability is None
    with pytest.raises(ValueError):
        DurabilityConfig(dir=str(tmp_path), fsync="sometimes")
    with pytest.raises(ValueError):
        DurabilityConfig(dir="")


# ---------------------------------------------------------------------------
# crash-injection matrix (subprocess SIGKILL at armed points)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("engine", ENGINES)
def test_crash_matrix(tmp_path, engine):
    """Every kill point recovers to exactly the acknowledged prefix —
    bit-identical to the oracle — on every engine.  The sharded engine
    runs its child under 4 forced devices (per-shard WALs), recovered
    elastically onto this process's single device."""
    n_dev = 4 if engine == "sharded" else 1
    results = crashkit.run_matrix(engine, str(tmp_path), n_devices=n_dev)
    assert len(results) == len(crashkit.matrix_points(engine, n_dev))
    # the post-checkpoint tail points actually replayed records
    by_point = {(r["point"], r["hits"]): r for r in results}
    assert by_point[("wal.append", 2)]["replayed_records"] >= 2


@pytest.mark.slow
def test_kill_recover_workload_replay(tmp_path):
    """ycsb_a kill-and-recover: replay half the stream, SIGKILL-equivalent
    abandon, recover, finish the stream on the recovered index — zero
    divergence from the oracle end to end."""
    rng = np.random.default_rng(11)
    keys = np.unique(rng.integers(0, 1 << 22, 3000)).astype(np.float64)
    ix = LearnedIndex.build(keys, config=IndexConfig(
        durability=DurabilityConfig(dir=str(tmp_path / "dur"),
                                    fsync="always")))
    spec = PRESETS["ycsb_a"].scaled(n_ops=3000, batch_size=128)
    batches = generate_stream(spec, keys)
    runner = WorkloadRunner(ix)
    out = runner.run_kill_recover(batches, kill_at=len(batches) // 2,
                                  spec=spec)
    runner.index.close()
    assert out["n_divergences"] == 0
    assert out["post_recovery_divergences"] == []
    assert out["recovery_s"] > 0


# ---------------------------------------------------------------------------
# hardened maintenance: bounded retries, degrade-to-sync, observability
# ---------------------------------------------------------------------------


def _flaky_merge_steps(oi, fail_times: int):
    """Wrap OnlineIndex._merge_steps to fail the first `fail_times` calls."""
    real = oi._merge_steps
    state = dict(left=fail_times, calls=0)

    def wrapped(*a, **kw):
        state["calls"] += 1
        if state["left"] > 0:
            state["left"] -= 1
            raise RuntimeError("injected merge fault")
        return real(*a, **kw)

    oi._merge_steps = wrapped
    return state


def test_background_merge_retries_then_succeeds():
    keys = np.arange(2048, dtype=np.float64)
    cfg = IndexConfig(maintenance=MaintenanceConfig(
        background=True, max_merge_retries=2, retry_backoff_s=0.001),
        telemetry=True)
    ix = LearnedIndex.build(keys, config=cfg)
    oi = ix._engine.oi
    state = _flaky_merge_steps(oi, fail_times=1)
    ix.upsert(keys[:600] + 0.5, np.arange(600, dtype=np.int64))
    st = ix.flush()                          # drains the worker
    assert state["calls"] >= 2               # failed once, retried, won
    assert st["pending_writes"] == 0
    assert not st["maint_degraded"]
    assert st["maint_errors"] == 0           # the retry succeeded: no
    #                                          scheduler-level failure
    m = ix.metrics()
    assert m["counters"]["maint.errors"] == 1
    assert m["spans"]["merge.failed"]["count"] == 1
    _, f = ix.lookup(keys[:600] + 0.5)
    assert f.all()
    ix.close()


def test_background_merge_exhaustion_degrades_to_sync():
    keys = np.arange(2048, dtype=np.float64)
    cfg = IndexConfig(maintenance=MaintenanceConfig(
        background=True, max_merge_retries=1, retry_backoff_s=0.001),
        telemetry=True)
    ix = LearnedIndex.build(keys, config=cfg)
    oi = ix._engine.oi
    state = _flaky_merge_steps(oi, fail_times=2)   # 1 + 1 retry both die
    ix.upsert(keys[:600] + 0.5, np.arange(600, dtype=np.int64))
    oi.merge("test")                         # submit to the worker
    oi.scheduler.drain()
    assert state["calls"] == 2
    st = ix.stats()
    assert st["maint_degraded"]
    assert st["maint_errors"] == 1           # one task failed after retries
    assert ix.metrics()["counters"]["maint.errors"] == 2
    # degraded => merges now run synchronously on the writer thread, and
    # the frozen overlay from the dead merge is reclaimed: still exact
    _, f = ix.lookup(keys[:600] + 0.5)
    assert f.all()
    st = ix.flush()
    assert st["pending_writes"] == 0 and st["maint_degraded"]
    _, f = ix.lookup(keys[:600] + 0.5)
    assert f.all()
    ix.close()


def test_sync_merge_failure_still_counts_errors():
    """The merge.failed span / maint.errors counter also fire on the
    synchronous path (no retries there: the caller sees the raise)."""
    keys = np.arange(1024, dtype=np.float64)
    ix = LearnedIndex.build(keys, config=IndexConfig(
        merge=manual_merge_policy(), telemetry=True,
        maintenance=MaintenanceConfig(max_merge_retries=3)))
    oi = ix._engine.oi
    state = _flaky_merge_steps(oi, fail_times=1)
    ix.upsert([0.5], [1])
    with pytest.raises(RuntimeError, match="injected merge fault"):
        ix.flush()
    assert state["calls"] == 1               # retry=False: no retry loop
    m = ix.metrics()
    assert m["counters"]["maint.errors"] == 1
    assert m["spans"]["merge.failed"]["count"] == 1
    assert not ix.stats()["maint_degraded"]
    assert ix.get(0.5) == 1                  # overlay still exact
    ix.flush()                               # next merge succeeds
    assert ix.stats()["pending_writes"] == 0
    ix.close()


# ---------------------------------------------------------------------------
# atomic save
# ---------------------------------------------------------------------------


def test_save_is_atomic_over_existing_file(tmp_path, monkeypatch):
    keys = np.arange(128, dtype=np.float64)
    ix = LearnedIndex.build(keys)
    path = str(tmp_path / "ix.npz")
    ix.save(path)
    before = open(path, "rb").read()

    def boom(*a, **kw):
        raise IOError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(IOError):
        ix.save(path)
    # the old file is untouched and no tmp litter remains
    assert open(path, "rb").read() == before
    assert os.listdir(str(tmp_path)) == ["ix.npz"]
    rx = LearnedIndex.load(path)
    np.testing.assert_array_equal(rx.items()[0], keys)


def test_load_truncated_file_raises_not_garbage(tmp_path):
    keys = np.arange(128, dtype=np.float64)
    ix = LearnedIndex.build(keys)
    path = str(tmp_path / "ix.npz")
    ix.save(path)
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[: len(blob) // 2])
    with pytest.raises(Exception):
        LearnedIndex.load(path)
