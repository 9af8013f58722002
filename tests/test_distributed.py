"""Distributed tests run in subprocesses with 8 forced host devices (the main
test process must keep seeing 1 device — dry-run contract)."""
import json
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
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_index_gather_and_a2a():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import build_sharded, to_mesh, sharded_lookup
        rng = np.random.default_rng(1)
        keys = np.unique(rng.lognormal(0, 1, 40000))
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
def test_sharded_online_updates():
    """Per-shard overlays absorb upserts/deletes without a global rebuild;
    merge republishes only the touched shards' rows."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import (build_sharded, to_mesh,
            sharded_lookup_with_overlay, sharded_upsert, sharded_delete,
            sharded_merge)
        rng = np.random.default_rng(2)
        keys = np.unique(rng.lognormal(0, 1, 20000))
        sd = build_sharded(keys, None, n_shards=8, sample_stride=4)
        mesh = jax.make_mesh((8,), ("data",))
        arrs = to_mesh(sd, mesh)
        qi = rng.integers(0, len(keys), 4096)
        q = jnp.asarray(keys[qi])
        new = np.setdiff1d(np.unique(rng.lognormal(0, 1, 3000)), keys)[:2048]
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
def test_sharded_range_query():
    """Per-shard sorted-pair bisection + prefix-offset psum assembly matches
    a brute-force numpy oracle, including windows spanning shard boundaries
    and max_hits truncation."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.distributed import (build_sharded, to_mesh,
            sharded_range_query)
        rng = np.random.default_rng(7)
        keys = np.unique(rng.lognormal(0, 1, 30000))
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


@pytest.mark.slow
def test_small_mesh_train_step_shardings():
    out = run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import model as MDL
        from repro.parallel import sharding as SH
        from repro.train import step as STEP
        from repro.train.optim import adamw
        cfg = dataclasses.replace(get_config("granite_8b").reduced(),
                                  d_model=128, n_heads=4, n_kv_heads=2,
                                  d_ff=256, vocab=512)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        opt = adamw(lr=1e-3)
        state_shape = jax.eval_shape(
            lambda: STEP.init_state(jax.random.PRNGKey(0), cfg, opt))
        p_sh = SH.param_shardings(cfg, mesh, state_shape["params"])
        # init on mesh
        with mesh:
            state = STEP.init_state(jax.random.PRNGKey(0), cfg, opt)
            step = jax.jit(STEP.make_train_step(cfg, opt))
            toks = jnp.zeros((8, 16), jnp.int32)
            batch = dict(tokens=toks, labels=toks)
            state2, m = step(state, batch)
            assert np.isfinite(float(m["loss"]))
        print("MESH-TRAIN-OK", float(m["loss"]))
    """)
    assert "MESH-TRAIN-OK" in out


@pytest.mark.slow
def test_elastic_restore_across_meshes(tmp_path):
    out = run_sub(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.ft import checkpoint as CKPT
        from repro.train import step as STEP
        from repro.train.optim import adamw
        cfg = get_config("granite_8b").reduced()
        opt = adamw()
        state = STEP.init_state(jax.random.PRNGKey(0), cfg, opt)
        CKPT.save(r"{tmp_path}", 5, state)
        # restore onto an 8-device mesh with FSDP shardings
        from repro.parallel import sharding as SH
        mesh = jax.make_mesh((8, 1), ("data", "model"))
        tmpl = jax.eval_shape(lambda: STEP.init_state(
            jax.random.PRNGKey(0), cfg, opt))
        p_sh = SH.param_shardings(cfg, mesh, tmpl["params"])
        got, man = CKPT.restore(r"{tmp_path}", tmpl["params"], p_sh,
                                prefix="params")
        assert man["step"] == 5
        leaf = got["layers"]["attn"]["wq"]
        assert len(leaf.sharding.device_set) >= 1
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(state["params"]["layers"]["attn"]["wq"]),
            rtol=1e-6)
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out


@pytest.mark.slow
def test_psum_int8_compression_collective():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.parallel.compression import psum_int8
        mesh = jax.make_mesh((8,), ("data",))
        def f(x):
            return psum_int8(x, "data")
        g = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (64, 32)),
                        jnp.float32)
        y = g(x)
        # every shard receives the same sum; compare against exact psum
        exact = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P("data"))(x)
        err = float(jnp.abs(y - exact).max())
        scale = float(jnp.abs(exact).max())
        assert err < 0.05 * scale + 0.1, (err, scale)
        print("COMPRESS-OK", err)
    """)
    assert "COMPRESS-OK" in out
