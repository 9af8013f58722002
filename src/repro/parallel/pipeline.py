"""Pipeline parallelism (GPipe schedule) over the multi-pod "pod" axis.

The layer stack is split into |pod| contiguous stages (stacked params get a
leading stage dim sharded over "pod").  A shard_map runs the classic GPipe
loop: M microbatches flow stage-to-stage via `ppermute`; each device step
computes its stage on the microbatch it currently holds.  Bubble fraction =
(S-1)/(M+S-1).  Used for the dense family; exercised by
tests/test_pipeline_pp.py and available to the dry-run via --set pp=1
(multi-pod mesh).

This is deliberately forward-oriented (training uses it through jax.grad —
autodiff of ppermute reverses the ring).  DP/TP compose: the body below only
touches the "pod" axis; batch stays sharded over "data" and TP over "model"
inside each stage exactly as in the non-PP path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import layers as L
from ..models import model as MDL
from ..models.config import ModelConfig


def split_stages(params, n_stages: int):
    """Reshape stacked layer params [L, ...] -> [S, L/S, ...]."""
    def one(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(one, params)


def pipeline_forward(cfg: ModelConfig, mesh: Mesh, params, tokens,
                     n_micro: int = 8):
    """Embedding + PP layer stack + head.  tokens: [B, S_len].

    params: full model params (layers stacked [L, ...]); embedding/head are
    replicated across stages (computed on stage 0 / last stage and passed
    through the ring with the activations).
    """
    n_stages = mesh.shape["pod"]
    staged = split_stages(params["layers"], n_stages)
    b, s = tokens.shape
    assert b % n_micro == 0

    x = L.embed(params["embed"], cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def stage_fn(stage_params, h):
        """Run this stage's layers on one microbatch of activations."""
        def body(carry, pl_):
            hh, _ = MDL._attn_block(pl_, cfg, carry, positions_mb)
            return hh, None
        positions_mb = jnp.broadcast_to(jnp.arange(h.shape[1]),
                                        (h.shape[0], h.shape[1]))
        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    mb = x.reshape(n_micro, b // n_micro, s, -1)

    def spmd(staged_params, mb):
        stage = jax.lax.axis_index("pod")
        sp = jax.tree.map(lambda t: t[0], staged_params)  # this stage's slice
        n_steps = n_micro + n_stages - 1
        buf = jnp.zeros_like(mb)            # outputs accumulated on last stage

        def step(carry, t):
            inflight, buf = carry
            # stage 0 injects microbatch t (if valid); others use inflight
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            injected = mb[mb_idx]
            h_in = jnp.where(stage == 0, injected, inflight)
            h_out = stage_fn(sp, h_in)
            # pass down the ring: stage i -> i+1
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            passed = jax.lax.ppermute(h_out, "pod", perm)
            # last stage writes its finished microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_valid = (t >= n_stages - 1) & (stage == n_stages - 1)
            buf = jnp.where(
                is_valid,
                jax.lax.dynamic_update_index_in_dim(buf, h_out, out_idx,
                                                    axis=0),
                buf)
            return (passed, buf), None

        inflight0 = jnp.zeros_like(mb[0])
        (_, buf), _ = jax.lax.scan(step, (inflight0, buf),
                                   jnp.arange(n_steps))
        # broadcast the last stage's buffer to everyone
        buf = jax.lax.psum(
            jnp.where(stage == n_stages - 1, buf, jnp.zeros_like(buf)),
            "pod")
        return buf

    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P("pod"), P()),
        out_specs=P(),
        check_vma=False)
    out = fn(staged, mb)                    # [n_micro, b/m, s, d]
    x = out.reshape(b, s, -1)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.lm_logits(params["embed"], cfg, x)
