"""Distributed tests run in subprocesses with 8 forced host devices (the main
test process must keep seeing 1 device).  Each runs on two key sets:
lognormal keys, and the local_hashed cells' YCSB record hashes
(`bench/keys.py`), whose online inserts are the next records' hashes."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_ENABLE_X64="1",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# `load_keys(rng, n)` and `new_keys(rng, keys)` (up to 2048 keys never
# loaded), defined ahead of each subprocess's code
KEY_MAKERS = {
    "lognormal": """
        def load_keys(rng, n):
            return np.unique(rng.lognormal(0, 1, n))
        def new_keys(rng, keys):
            return np.setdiff1d(np.unique(rng.lognormal(0, 1, 3000)),
                                keys)[:2048]
    """,
    "ycsb_hashed": """
        from tests.conftest import ycsb_keys
        def load_keys(rng, n):
            return ycsb_keys("hashed", n, 0)[0]
        def new_keys(rng, keys):
            return ycsb_keys("hashed", len(keys), 2048)[1]
    """,
}


@pytest.mark.slow
@pytest.mark.parametrize("shape", sorted(KEY_MAKERS))
def test_sharded_index_gather_and_a2a(shape):
    out = run_sub(KEY_MAKERS[shape] + """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import build_sharded, to_mesh, sharded_lookup
        rng = np.random.default_rng(1)
        keys = load_keys(rng, 40000)
        sd = build_sharded(keys, None, n_shards=8, sample_stride=4)
        mesh = jax.make_mesh((8,), ("data",))
        arrs = to_mesh(sd, mesh)
        qi = rng.integers(0, len(keys), 4096)
        q = jnp.asarray(keys[qi])
        v, f = sharded_lookup(mesh, arrs, q, sd.max_depth, strategy="gather")
        assert bool(np.asarray(f).all())
        assert np.array_equal(np.asarray(v), qi)
        v2, f2, ovf = sharded_lookup(mesh, arrs, q, sd.max_depth, strategy="a2a")
        ok = np.asarray(f2)
        assert np.array_equal(np.asarray(v2)[ok], qi[ok])
        assert ok.mean() > 0.99
        print("DIST-OK", int(np.asarray(ovf).sum()))
    """)
    assert "DIST-OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("shape", sorted(KEY_MAKERS))
def test_sharded_online_updates(shape):
    """Per-shard overlays absorb upserts/deletes without a global rebuild;
    merge republishes only the touched shards' rows."""
    out = run_sub(KEY_MAKERS[shape] + """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import (build_sharded, to_mesh,
            sharded_lookup_with_overlay, sharded_upsert, sharded_delete,
            sharded_merge)
        rng = np.random.default_rng(2)
        keys = load_keys(rng, 20000)
        sd = build_sharded(keys, None, n_shards=8, sample_stride=4)
        mesh = jax.make_mesh((8,), ("data",))
        arrs = to_mesh(sd, mesh)
        qi = rng.integers(0, len(keys), 4096)
        q = jnp.asarray(keys[qi])
        new = new_keys(rng, keys)
        sharded_upsert(sd, new, 5_000_000 + np.arange(len(new)))
        dels = np.unique(keys[qi[:512]])
        sharded_delete(sd, dels)
        # exact between merges: overlay keys found, tombstoned keys hidden
        v, f = sharded_lookup_with_overlay(mesh, arrs, sd, q, sd.max_depth)
        f = np.asarray(f); deleted = np.isin(keys[qi], dels)
        assert not f[deleted].any() and f[~deleted].all()
        qn = jnp.asarray(new[:1024])
        vn, fn = sharded_lookup_with_overlay(mesh, arrs, sd, qn, sd.max_depth)
        assert np.asarray(fn).all()
        assert np.array_equal(np.asarray(vn), 5_000_000 + np.arange(1024))
        # merge: fold per-shard overlays through Alg. 7/8, republish rows
        merged = sharded_merge(sd)
        assert merged and sd.epoch == 1
        assert all(ov.count == 0 for ov in sd.overlays)
        arrs = to_mesh(sd, mesh)
        v3, f3 = sharded_lookup_with_overlay(mesh, arrs, sd, qn, sd.max_depth)
        assert np.asarray(f3).all()
        assert np.array_equal(np.asarray(v3), 5_000_000 + np.arange(1024))
        v4, f4 = sharded_lookup_with_overlay(mesh, arrs, sd, q, sd.max_depth)
        f4 = np.asarray(f4)
        assert not f4[deleted].any() and f4[~deleted].all()
        print("DIST-ONLINE-OK", sd.epoch)
    """)
    assert "DIST-ONLINE-OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("shape", sorted(KEY_MAKERS))
def test_sharded_range_query(shape):
    """Per-shard sorted-pair bisection + prefix-offset psum assembly matches
    a brute-force numpy oracle, including windows spanning shard boundaries
    and max_hits truncation."""
    out = run_sub(KEY_MAKERS[shape] + """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import (build_sharded, to_mesh,
            sharded_range_query)
        rng = np.random.default_rng(7)
        keys = load_keys(rng, 30000)
        sd = build_sharded(keys, None, n_shards=8, sample_stride=4)
        mesh = jax.make_mesh((8,), ("data",))
        arrs = to_mesh(sd, mesh)
        # windows: random; some straddle shard boundaries, some overflow
        starts = rng.integers(0, len(keys) - 200, 512)
        widths = rng.integers(0, 180, 512)
        b_idx = np.searchsorted(keys, sd.boundaries[1:-1])
        starts[:64] = np.clip(b_idx[rng.integers(0, len(b_idx), 64)] - 20,
                              0, len(keys) - 200)       # straddle boundaries
        lo = keys[starts]
        hi = keys[np.minimum(starts + widths, len(keys) - 1)]
        ks, vs, cnt = sharded_range_query(mesh, arrs, jnp.asarray(lo),
                                          jnp.asarray(hi), max_hits=128)
        ks, vs, cnt = np.asarray(ks), np.asarray(vs), np.asarray(cnt)
        for i in range(512):
            m = (keys >= lo[i]) & (keys < hi[i])
            ek = keys[m][:128]; ev = np.nonzero(m)[0][:128]
            assert cnt[i] == len(ek), (i, cnt[i], len(ek))
            assert np.array_equal(ks[i][:cnt[i]], ek), i
            assert np.array_equal(vs[i][:cnt[i]], ev), i
            assert np.all(ks[i][cnt[i]:] == np.inf), i
        print("DIST-RANGE-OK")
    """)
    assert "DIST-RANGE-OK" in out
