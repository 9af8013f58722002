"""Batched device search (core/search.py): flat snapshot vs host truth,
FMA-consistency regression, overlay, range queries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import search as S
from repro.core.dili import bulk_load
from repro.core.flat import DeltaOverlay, flatten
from tests.conftest import make_keys


@pytest.fixture(scope="module", params=["logn", "uniform", "fb", "wikits"])
def snap(request):
    rng = np.random.default_rng(11)
    keys = make_keys(request.param, 25000, rng)
    d = bulk_load(keys)
    f = flatten(d)
    return keys, d, f, S.device_arrays(f)


def test_search_batch_hits(snap):
    keys, d, f, idx = snap
    rng = np.random.default_rng(12)
    qi = rng.integers(0, len(keys), 8192)
    v, fnd = S.search_batch(idx, jnp.asarray(keys[qi]),
                            max_depth=f.max_depth + 2)
    assert bool(np.asarray(fnd).all())
    assert np.array_equal(np.asarray(v), qi)


def test_search_batch_misses(snap):
    keys, d, f, idx = snap
    rng = np.random.default_rng(13)
    qi = rng.integers(0, len(keys) - 1, 4096)
    mids = (keys[qi] + keys[qi + 1]) / 2
    ok = (mids != keys[qi]) & (mids != keys[qi + 1])
    v, fnd = S.search_batch(idx, jnp.asarray(mids),
                            max_depth=f.max_depth + 2)
    assert not np.asarray(fnd)[ok].any()


def test_fma_consistency(snap):
    """jit vs eager must agree — regression for the FMA-contraction bug
    (construction nudges every model off integer boundaries)."""
    keys, d, f, idx = snap
    rng = np.random.default_rng(14)
    q = jnp.asarray(keys[rng.integers(0, len(keys), 4096)])
    v1, f1 = S.search_batch(idx, q, max_depth=f.max_depth + 2)
    with jax.disable_jit():
        v2, f2 = S.search_batch(idx, q, max_depth=f.max_depth + 2)
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(f1), np.asarray(f2))


def test_stats_probe_counts(snap):
    keys, d, f, idx = snap
    rng = np.random.default_rng(15)
    q = jnp.asarray(keys[rng.integers(0, len(keys), 1024)])
    v, fnd, nodes, probes = S.search_batch(idx, q, max_depth=f.max_depth + 2,
                                           with_stats=True)
    nodes = np.asarray(nodes)
    assert bool(np.asarray(fnd).all())
    assert nodes.min() >= 2 and nodes.max() <= f.max_depth + 1


def test_overlay_lookup(snap):
    keys, d, f, idx = snap
    ov = DeltaOverlay.empty(1024)
    newk = np.array([keys[0] - 5.0, keys[-1] + 5.0])
    ov = ov.insert_batch(newk, np.array([111, 222]))
    ova = S.overlay_arrays(ov)
    v, fnd = S.search_with_overlay(idx, ova, jnp.asarray(newk),
                                   max_depth=f.max_depth + 2)
    assert bool(np.asarray(fnd).all())
    assert list(np.asarray(v)) == [111, 222]
    # snapshot keys still resolve through the combined path
    v2, f2 = S.search_with_overlay(idx, ova, jnp.asarray(keys[:64]),
                                   max_depth=f.max_depth + 2)
    assert bool(np.asarray(f2).all())


def test_overlay_vals_int64_roundtrip(snap):
    """Overlay payloads above 2^31 must not wrap (overlay_arrays regression)."""
    keys, d, f, idx = snap
    big = 2**40 + 123
    ov = DeltaOverlay.empty(64).insert_batch(
        np.array([keys[-1] + 9.0]), np.array([big]))
    ova = S.overlay_arrays(ov)
    assert ova["vals"].dtype == jnp.int64
    v, fnd = S.search_with_overlay(idx, ova, jnp.asarray([keys[-1] + 9.0]),
                                   max_depth=f.max_depth + 2)
    assert bool(np.asarray(fnd)[0])
    assert int(np.asarray(v)[0]) == big


def test_search_with_overlay_precedence(snap):
    """Overlay wins over the snapshot; a tombstone hides a snapshot hit."""
    from repro.online.overlay import TombstoneOverlay, overlay_device_arrays
    keys, d, f, idx = snap
    ov = TombstoneOverlay.empty(64)
    ov = ov.upsert_batch([keys[5]], [999_000])   # overwrite a snapshot key
    ov = ov.delete_batch([keys[6]])              # tombstone a snapshot key
    ova = overlay_device_arrays(ov)
    q = jnp.asarray([keys[5], keys[6], keys[7]])
    v, fnd = S.search_with_overlay(idx, ova, q, max_depth=f.max_depth + 2)
    v, fnd = np.asarray(v), np.asarray(fnd)
    assert fnd[0] and v[0] == 999_000            # overlay beats snapshot val
    assert not fnd[1]                            # tombstone hides the hit
    assert fnd[2] and v[2] == 7                  # untouched key unaffected


def test_republish_after_updates(snap):
    keys, d, f, idx = snap
    rng = np.random.default_rng(16)
    new = np.setdiff1d(np.unique(rng.uniform(keys[10], keys[-10], 500)), keys)
    for j, k in enumerate(new):
        d.insert(float(k), 7_000_000 + j)
    for k in keys[:100]:
        d.delete(float(k))
    f2 = flatten(d)
    idx2 = S.device_arrays(f2)
    v, fnd = S.search_batch(idx2, jnp.asarray(new), max_depth=f2.max_depth + 2)
    assert bool(np.asarray(fnd).all())
    v3, f3 = S.search_batch(idx2, jnp.asarray(keys[:100]),
                            max_depth=f2.max_depth + 2)
    assert not np.asarray(f3).any()


def _scan_lengths(closed_jaxpr) -> list:
    """All lax.scan trip counts reachable from a jaxpr (recursing through
    pjit / scan / while / custom calls)."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                out.append(int(eqn.params["length"]))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):          # ClosedJaxpr
                    walk(v.jaxpr)
                elif hasattr(v, "eqns"):         # raw Jaxpr
                    walk(v)
    walk(closed_jaxpr.jaxpr)
    return out


def test_traversal_depth_exact_not_24(snap):
    """Regression: the traversal scan length must be the snapshot's true
    max_depth (derived via resolve_max_depth), not a hard-coded 24-trip
    worst case — and exactly max_depth trips must already find every key."""
    keys, d, f, idx = snap
    assert S.resolve_max_depth(idx) == f.max_depth
    rng = np.random.default_rng(17)
    q = jnp.asarray(keys[rng.integers(0, len(keys), 2048)])
    v, fnd = S.search_batch(idx, q)          # depth derived from the snapshot
    assert bool(np.asarray(fnd).all())
    lengths = _scan_lengths(
        jax.make_jaxpr(lambda q: S.search_batch(idx, q))(q))
    assert f.max_depth in lengths            # traversal is depth-exact
    # nothing scans 24 trips (or anything beyond the dense-probe phases)
    assert all(ln <= max(16, f.max_depth) for ln in lengths), lengths


def test_early_exit_matches_scan(snap):
    """The batch-convergence while_loop variant is bit-identical to the
    fixed-trip scan, including stats."""
    keys, d, f, idx = snap
    rng = np.random.default_rng(18)
    mids = (keys[:-1] + keys[1:]) / 2        # mix hits and misses
    q = jnp.asarray(np.concatenate([keys[rng.integers(0, len(keys), 1024)],
                                    mids[rng.integers(0, len(mids), 1024)]]))
    v1, f1 = S.search_batch(idx, q, early_exit=False)
    v2, f2 = S.search_batch(idx, q, early_exit=True)
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    s1 = S.search_batch(idx, q, with_stats=True, early_exit=False)
    s2 = S.search_batch(idx, q, with_stats=True, early_exit=True)
    for a, b in zip(s1, s2):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_resolve_max_depth_rejects_tracers(snap):
    keys, d, f, idx = snap
    with pytest.raises(TypeError):
        jax.jit(lambda i: S.resolve_max_depth(i))(idx)


def test_fused_overlay_single_dispatch(snap):
    """search_with_overlay is ONE jitted computation: its jaxpr top level is
    a single jitted call (traversal + overlay resolution fused).  The call
    is recognised by its nested `jaxpr` parameter, not by the primitive's
    name, which differs across jax releases."""
    from repro.online.overlay import TombstoneOverlay, overlay_device_arrays
    keys, d, f, idx = snap
    ova = overlay_device_arrays(
        TombstoneOverlay.empty(16).upsert_batch([keys[3]], [42]))
    q = jnp.asarray(keys[:8])
    jaxpr = jax.make_jaxpr(
        lambda q: S.search_with_overlay(idx, ova, q, f.max_depth))(q)
    eqns = jaxpr.jaxpr.eqns
    assert len(eqns) == 1 and "jaxpr" in eqns[0].params, \
        [e.primitive.name for e in eqns]
    v, fnd = S.search_with_overlay(idx, ova, q)
    assert bool(np.asarray(fnd).all())
    assert int(np.asarray(v)[3]) == 42


def test_range_query_batch(snap):
    keys, d, f, idx = snap
    lo = jnp.asarray([keys[50], keys[500]])
    hi = jnp.asarray([keys[80], keys[520]])
    ks, vs, counts = S.range_query_batch(idx, lo, hi, max_hits=64)
    counts = np.asarray(counts)
    assert counts[0] == 30 and counts[1] == 20
    got = np.asarray(ks[0])[:30]
    assert np.array_equal(got, keys[50:80])


def test_range_query_batch_matches_host(snap):
    """Exact agreement with host DILI.range_query on random windows.

    Re-flattens at test time: the module-scoped host `d` may have absorbed
    updates from earlier tests, which also exercises ranges post-update."""
    keys, d, _, _ = snap
    f = flatten(d)
    idx = S.device_arrays(f)
    rng = np.random.default_rng(21)
    starts = rng.integers(0, len(keys) - 120, 16)
    widths = rng.integers(1, 100, 16)
    lo = keys[starts]
    hi = keys[np.minimum(starts + widths, len(keys) - 1)]
    ks, vs, counts = S.range_query_batch(idx, jnp.asarray(lo),
                                         jnp.asarray(hi), max_hits=256)
    ks, vs, counts = np.asarray(ks), np.asarray(vs), np.asarray(counts)
    for i in range(len(lo)):
        expect = d.range_query(float(lo[i]), float(hi[i]))
        assert counts[i] == len(expect)
        got_k = ks[i][: counts[i]]
        got_v = vs[i][: counts[i]]
        assert np.array_equal(got_k, [p[0] for p in expect])
        assert np.array_equal(got_v, [p[1] for p in expect])


def test_range_query_batch_max_hits_truncation(snap):
    """Overflowing windows truncate: count saturates at max_hits and every
    returned (key, val) is a true member of the host result."""
    keys, d, _, _ = snap
    idx = S.device_arrays(flatten(d))
    lo, hi = float(keys[200]), float(keys[500])     # ~300 pairs > max_hits=32
    ks, vs, counts = S.range_query_batch(idx, jnp.asarray([lo]),
                                         jnp.asarray([hi]), max_hits=32)
    counts = np.asarray(counts)
    assert counts[0] == 32
    expect = dict(d.range_query(lo, hi))
    got_k = np.asarray(ks[0])
    got_v = np.asarray(vs[0])
    assert np.all(np.diff(got_k) >= 0)              # sorted ascending
    for k, v in zip(got_k, got_v):
        assert k in expect and expect[k] == v
