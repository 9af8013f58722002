"""Engine equivalence (acceptance): the same key set served through
`LocalEngine`, `PallasEngine`, and `ShardedEngine` must answer lookups,
range queries, and delete-visibility identically at every lifecycle point
(fresh build / overlay-pending / post-flush).

f32 tolerance rule: the Pallas engine quantizes keys to f32 at the
boundary, so the shared key set is integer-valued below 2^24 (exactly
f32-representable) and payloads stay below 2^31 (the kernel path's int32
payload width) — under those conditions the engines must agree bit-exactly,
not approximately.
"""
import numpy as np
import pytest

from repro.api import (IndexConfig, LearnedIndex, MaintenanceConfig,
                       manual_merge_policy)

ENGINES = ("local", "pallas", "sharded")


def _keyset(rng):
    # integer-valued f64 keys < 2^24: exact under the pallas engine's f32
    # quantization; payloads < 2^31: exact under the kernel's int32 vals
    keys = np.unique(rng.integers(0, 1 << 22, 4000)).astype(np.float64)
    vals = rng.integers(0, 1 << 30, len(keys)).astype(np.int64)
    return keys, vals


@pytest.fixture(scope="module")
def fleet():
    rng = np.random.default_rng(99)
    keys, vals = _keyset(rng)
    cfg = IndexConfig(merge=manual_merge_policy(), overlay_cap=256)
    ixs = {e: LearnedIndex.build(keys, vals, config=cfg.with_engine(e))
           for e in ENGINES}
    return keys, vals, ixs, rng


def _assert_lookup_equivalent(ixs, queries):
    ref_v, ref_f = ixs["local"].lookup(queries)
    for e in ENGINES[1:]:
        v, f = ixs[e].lookup(queries)
        np.testing.assert_array_equal(f, ref_f, err_msg=e)
        np.testing.assert_array_equal(v[f], ref_v[ref_f], err_msg=e)
    return ref_v, ref_f


def _assert_range_equivalent(ixs, lo, hi, max_hits=64):
    ref = ixs["local"].range(lo, hi, max_hits=max_hits)
    for e in ENGINES[1:]:
        ks, vs, cnt = ixs[e].range(lo, hi, max_hits=max_hits)
        np.testing.assert_array_equal(cnt, ref[2], err_msg=e)
        np.testing.assert_array_equal(ks, ref[0], err_msg=e)
        np.testing.assert_array_equal(vs, ref[1], err_msg=e)
    return ref


def test_lookup_equivalence_fresh(fleet):
    keys, vals, ixs, rng = fleet
    qi = rng.integers(0, len(keys), 2048)
    # the +2^23 shift pushes queries past every key: guaranteed misses
    q = np.concatenate([keys[qi], keys[qi[:16]] + (1 << 23)])
    v, f = _assert_lookup_equivalent(ixs, q)
    assert f[: len(qi)].all()
    np.testing.assert_array_equal(v[: len(qi)], vals[qi])


def test_range_equivalence_fresh(fleet):
    keys, vals, ixs, rng = fleet
    starts = rng.integers(0, len(keys) - 100, 128)
    lo, hi = keys[starts], keys[starts + rng.integers(1, 90, 128)]
    ks, vs, cnt = _assert_range_equivalent(ixs, lo, hi)
    # oracle: brute force over the host key set
    for i in range(0, 128, 17):
        want = keys[(keys >= lo[i]) & (keys < hi[i])][:64]
        assert cnt[i] == len(want)
        np.testing.assert_array_equal(ks[i][: cnt[i]], want)


def test_write_and_delete_visibility_equivalence(fleet):
    keys, vals, ixs, rng = fleet
    new = np.setdiff1d(np.arange(1, 200, dtype=np.float64) * 7 + (1 << 22),
                       keys)[:96]
    new_v = np.arange(len(new), dtype=np.int64) + 5_000_000
    dead = keys[rng.integers(0, len(keys), 64)]
    for ix in ixs.values():
        ix.upsert(new, new_v)
        ix.delete(dead)

    probe = np.concatenate([new, dead, keys[:256]])
    # pending state: upserts visible, tombstones hide snapshot hits
    v, f = _assert_lookup_equivalent(ixs, probe)
    assert f[: len(new)].all()
    assert not f[len(new): len(new) + len(dead)].any()

    # ranges spanning the written region agree too (overlay-exact)
    lo = np.array([new[0] - 3, keys[0], dead.min() - 1])
    hi = np.array([new[-1] + 3, keys[300], dead.min() + 1])
    _assert_range_equivalent(ixs, lo, hi)

    # post-flush: folded through Alg. 7/8 on every engine
    for ix in ixs.values():
        ix.flush()
        assert ix.stats()["pending_writes"] == 0
    v, f = _assert_lookup_equivalent(ixs, probe)
    assert f[: len(new)].all()
    assert not f[len(new): len(new) + len(dead)].any()
    _assert_range_equivalent(ixs, lo, hi)

    # logical content identical across engines
    k0, v0 = ixs["local"].items()
    for e in ENGINES[1:]:
        k, v = ixs[e].items()
        np.testing.assert_array_equal(k, k0, err_msg=e)
        np.testing.assert_array_equal(v, v0, err_msg=e)


STATS_CONTRACT = frozenset((
    "engine", "epoch", "max_depth", "snapshot_keys", "pending_writes",
    "overlay_live", "overlay_tombstones", "overlay_cap", "overlay_fill",
    "n_flattens", "n_merges", "device_bytes",
    # maintenance counters (PR 5): every engine reports them, with or
    # without a MaintenanceConfig
    "n_full_flattens", "n_incremental_flattens", "n_retrains",
    "dirty_row_fraction", "maint_queue_depth", "maint_errors",
    # retry exhaustion flag (PR 7): background merges degraded to sync
    "maint_degraded"))


def test_stats_contract_equivalence():
    """Every engine reports the same stats keys with the same meanings:
    epoch counts device publishes (1 after build, +1 per effective flush —
    the sharded engine used to count merges from 0), and the overlay
    breakdown (pending/live/tombstones/cap/fill) is identical for the same
    write history on all three engines."""
    rng = np.random.default_rng(42)
    keys = np.unique(rng.integers(0, 1 << 21, 1200)).astype(np.float64)
    cfg = IndexConfig(merge=manual_merge_policy(), overlay_cap=128)
    ixs = {e: LearnedIndex.build(keys, config=cfg.with_engine(e))
           for e in ENGINES}
    for e, ix in ixs.items():
        s = ix.stats()
        assert STATS_CONTRACT <= set(s), e
        assert s["epoch"] == 1 and ix.epoch == 1, e
        assert (s["pending_writes"], s["overlay_live"],
                s["overlay_tombstones"], s["overlay_fill"]) == (0, 0, 0, 0.0)

    new = np.setdiff1d(keys[:50] + 1.0, keys)      # 50 fresh integer keys
    dead = np.unique(keys[rng.integers(100, 900, 64)])
    for ix in ixs.values():
        ix.upsert(new, np.arange(len(new), dtype=np.int64))
        ix.delete(dead)
    ref = ixs["local"].stats()
    assert ref["pending_writes"] == len(new) + len(dead)
    assert ref["overlay_live"] == len(new)
    assert ref["overlay_tombstones"] == len(dead)
    for e in ENGINES[1:]:
        s = ixs[e].stats()
        for k in ("pending_writes", "overlay_live", "overlay_tombstones"):
            assert s[k] == ref[k], (e, k)
        assert s["overlay_cap"] >= s["pending_writes"], e
        assert 0.0 < s["overlay_fill"] <= 1.0, e
    # sharded: per-shard breakdown sums to the total (the old stats path
    # had no per-shard visibility at all)
    sh = ixs["sharded"].stats()
    assert sum(sh["per_shard_pending"]) == sh["pending_writes"]

    for e, ix in ixs.items():
        ix.flush()
        s = ix.stats()
        assert s["epoch"] == 2 and ix.epoch == 2, e
        assert (s["pending_writes"], s["overlay_fill"]) == (0, 0.0), e
        assert s["n_merges"] == 1, e
        # an empty flush must NOT bump the publish epoch on any engine
        ix.flush()
        assert ix.stats()["epoch"] == 2, e
        # without a MaintenanceConfig every flatten is a full one and the
        # maintenance counters sit at their legacy values
        s = ix.stats()
        assert s["n_incremental_flattens"] == 0, e
        assert s["n_full_flattens"] == s["n_flattens"], e
        assert (s["n_retrains"], s["maint_queue_depth"],
                s["maint_errors"]) == (0, 0, 0), e
        assert s["dirty_row_fraction"] == 1.0, e


def test_stats_maintenance_counters_equivalence():
    """With a (synchronous) MaintenanceConfig, every engine reports the
    same maintenance-counter semantics: a post-build merge flattens
    incrementally (splice), full-flatten count stays at the build count,
    and the dirty-row fraction reflects a partial re-materialization."""
    rng = np.random.default_rng(7)
    # irregular gaps => a multi-segment tree (uniform keys collapse to one
    # perfect leaf, where splice == full by construction); even integers
    # keep the pallas f32 convention
    keys = np.unique(rng.integers(0, 1 << 21, 6000)).astype(np.float64) * 2
    cfg = IndexConfig(merge=manual_merge_policy(), overlay_cap=256,
                      maintenance=MaintenanceConfig(retrain=False))
    for e in ENGINES:
        ix = LearnedIndex.build(keys, config=cfg.with_engine(e))
        builds = ix.stats()["n_full_flattens"]
        assert builds >= 1 and ix.stats()["n_incremental_flattens"] == 0, e
        # hot-spot writes: only a narrow key region gets dirty
        hot = (rng.integers(0, 60, 64) * 2 + 1).astype(np.float64)
        ix.upsert(hot, np.arange(len(hot), dtype=np.int64))
        ix.flush()
        # the first maintained merge seeds the segment cache: full on a
        # cold flattener, incremental from then on
        hot2 = hot[:32]
        ix.upsert(hot2, np.arange(len(hot2), dtype=np.int64))
        ix.flush()
        s = ix.stats()
        assert s["n_incremental_flattens"] >= 1, (e, s)
        assert s["n_retrains"] == 0 and s["maint_errors"] == 0, e
        assert 0.0 < s["dirty_row_fraction"] <= 1.0, e
        assert s["dirty_row_fraction"] < 1.0, (e, s)   # hot-spot => partial
        assert len(ix.maint_timings()) >= 1, e
        ix.close()


def test_pallas_engine_large_magnitude_keys_exact():
    """Regression: at 1.6e9 key magnitude f32 ulp is 128, the section-7
    nudge is unattainable, and compiled XLA single-rounds `a + b*q` past
    the barrier — boundary queries used to mis-route by one child and
    miss.  The pair-table recheck must make every present key findable."""
    rng = np.random.default_rng(1)
    steps = rng.integers(1, 4, 20000).astype(np.float64)
    keys = np.unique(1.6e9 + np.cumsum(steps))
    ix = LearnedIndex.build(keys, config=IndexConfig(
        engine="pallas", sample_stride=4, merge=manual_merge_policy()))
    k32 = np.unique(keys.astype(np.float32)).astype(np.float64)
    v, f = ix.lookup(k32)
    assert f.all(), f"{int((~f).sum())} f32 ULP misses"
    assert ix.get(float(k32[len(k32) // 2])) is not None
    # absent keys must still miss (recheck adds no false positives);
    # offsets far beyond the f32 spacing (128 at this magnitude)
    _, f2 = ix.lookup([keys[0] - 5e5, keys[-1] + 5e5])
    assert not f2.any()


def test_pallas_engine_rejects_integer_writes_beyond_f32_domain():
    """Satellite regression: at |key| >= 2**24 the f32 spacing exceeds 1,
    so adjacent int64 keys alias to one f32 value — a write there would
    silently land on a DIFFERENT logical key.  The engine must refuse it
    (naming the precision domain), not quantize it; in-domain integers and
    fractional keys (the documented quantize tolerance) still pass."""
    U = np.arange(0, 4000, 2, dtype=np.float64)
    ix = LearnedIndex.build(U, config=IndexConfig(
        engine="pallas", merge=manual_merge_policy()))
    bad = np.array([2.0 ** 25 + 1])            # f32 spacing here is 4
    with pytest.raises(ValueError, match="16777216"):
        ix.upsert(bad, np.array([7]))
    with pytest.raises(ValueError, match="f32"):
        ix.delete(bad)
    ix.upsert(np.array([3.0, 2.0 ** 24 - 2.0]), np.array([1, 2]))
    ix.upsert(np.array([5.25]), np.array([3]))     # fractional: tolerated
    assert ix.get(2.0 ** 24 - 2.0) == 2
    assert ix.get(5.25) == 3
    ix.close()


def test_pallas_engine_warns_on_build_key_collisions():
    """Satellite regression: building the pallas engine over keys that
    collide after f32 quantization is tolerated (last-write-wins) but
    must WARN, stating the f32 integer-precision domain (2**24)."""
    keys = 2.0 ** 25 + np.arange(64, dtype=np.float64)   # collapse 4:1
    with pytest.warns(UserWarning, match="16777216"):
        ix = LearnedIndex.build(keys, config=IndexConfig(
            engine="pallas", merge=manual_merge_policy()))
    v, f = ix.lookup(np.array([2.0 ** 25]))
    assert f.all()
    ix.close()


@pytest.mark.parametrize("bulk_kw,share", [
    (dict(local_optimized=False), 1.0),    # DILI-LO: every leaf is dense
    ({}, 0.0),            # small ordered ids: f32 placement needs no dense leaf
])
def test_pallas_stats_dense_leaf_key_share(bulk_kw, share):
    ix = LearnedIndex.build(np.arange(6000.0), config=IndexConfig(
        engine="pallas", merge=manual_merge_policy(), bulk_kw=bulk_kw))
    assert ix.stats()["dense_leaf_key_share"] == share
    ix.close()


def test_pallas_dense_leaf_key_share_follows_publish():
    """The share is the new snapshot's after a merge empties part of a
    dense leaf: the pairs its dense leaves hold over all pairs."""
    from repro.core.dili import Leaf
    from repro.core.flat import preorder
    keys = np.arange(20000.0)   # f32 placement packs the top keys densely
    ix = LearnedIndex.build(keys, config=IndexConfig(
        engine="pallas", merge=manual_merge_policy()))

    def dense_leaves():
        return [nd for nd in preorder(ix.host.root)
                if isinstance(nd, Leaf) and nd.dense]

    held = sum(nd.omega for nd in dense_leaves())
    assert 0 < held < len(keys)
    assert ix.stats()["dense_leaf_key_share"] == held / len(keys)
    lb = dense_leaves()[0].lb
    ix.delete(np.arange(lb, lb + 1000))
    ix.flush()
    assert sum(nd.omega for nd in dense_leaves()) == held - 1000
    assert (ix.stats()["dense_leaf_key_share"]
            == (held - 1000) / (len(keys) - 1000))
    ix.close()


@pytest.mark.slow
def test_sharded_engine_multi_device_equivalence():
    """The facade on an 8-shard mesh answers exactly like the local engine
    (subprocess: the main test process must keep seeing 1 device)."""
    from tests.test_distributed import run_sub
    out = run_sub("""
        import numpy as np
        from repro.api import (IndexConfig, LearnedIndex, MaintenanceConfig,
                       manual_merge_policy)
        rng = np.random.default_rng(5)
        keys = np.unique(rng.integers(0, 1 << 22, 20000)).astype(np.float64)
        cfg = IndexConfig(merge=manual_merge_policy())
        a = LearnedIndex.build(keys, config=cfg)
        b = LearnedIndex.build(keys, config=cfg.with_engine("sharded"))
        assert b.stats()["n_shards"] == 8
        q = np.concatenate([keys[rng.integers(0, len(keys), 4000)],
                            keys[:100] + 0.5])
        for ix in (a, b):
            ix.upsert(keys[:50] + 0.25, np.arange(50))
            ix.delete(keys[100:150])
        # stats contract on a REAL multi-shard mesh: totals match the
        # local engine, per-shard pending sums to the total, publish-epoch
        # semantics agree
        sa, sb = a.stats(), b.stats()
        for k in ("pending_writes", "overlay_live", "overlay_tombstones",
                  "epoch"):
            assert sa[k] == sb[k], (k, sa[k], sb[k])
        assert sb["pending_writes"] == 100
        assert sum(sb["per_shard_pending"]) == 100
        assert len(sb["per_shard_pending"]) == 8
        va, fa = a.lookup(q); vb, fb = b.lookup(q)
        assert np.array_equal(fa, fb) and np.array_equal(va[fa], vb[fb])
        lo = keys[rng.integers(0, len(keys) - 200, 256)]
        ra = a.range(lo, lo + 5000, max_hits=32)
        rb = b.range(lo, lo + 5000, max_hits=32)
        for x, y in zip(ra, rb):
            assert np.array_equal(x, y)
        a.flush(); b.flush()
        assert a.stats()["epoch"] == b.stats()["epoch"] == 2
        assert b.stats()["pending_writes"] == 0
        va, fa = a.lookup(q); vb, fb = b.lookup(q)
        assert np.array_equal(fa, fb) and np.array_equal(va[fa], vb[fb])
        # a2a with a skewed batch: bucket overflow must fall back to the
        # exact gather path, not silently report misses
        c = LearnedIndex.build(keys, config=cfg.with_engine("sharded"),
                               lookup_strategy="a2a")
        lo_shard = keys[keys < np.quantile(keys, 1.0 / 8)]
        skew = lo_shard[rng.integers(0, len(lo_shard), 1024)]
        vs_, fs_ = c.lookup(skew)
        assert fs_.all(), f"a2a overflow dropped {int((~fs_).sum())} lanes"
        print("API-SHARDED-OK")
    """)
    assert "API-SHARDED-OK" in out
