"""Serving launcher: prefill/decode engine + DILI session table behind
the concurrent serving front-end (DESIGN.md section 15).

Session admits/evicts/lookups no longer call the index facade directly:
a `ServeFrontend` batches them through `repro.serve`, and the admit/evict
bookkeeping for each decode batch runs on `--frontend-threads` concurrent
client threads — the same shape a real deployment has (many request
handlers, one batcher, one index writer).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --reduced \\
        --requests 16 --tokens 8 --frontend-threads 4
"""

from __future__ import annotations

import argparse
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models import model as MDL
from ..serve.frontend import ServeFrontend
from ..serve.sessions import SessionTable
from ..train import step as STEP


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--frontend-threads", type=int, default=4,
                    help="concurrent session-admission threads")
    args = ap.parse_args()
    # the session table's index keys are f64; the model uses explicit dtypes
    jax.config.update("jax_enable_x64", True)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = MDL.init_params(jax.random.PRNGKey(0), cfg)
    prefill = jax.jit(STEP.make_prefill_step(cfg))
    decode = jax.jit(STEP.make_decode_step(cfg))
    sessions = SessionTable(n_slots=args.batch + 4)
    frontend = ServeFrontend(sessions.index)
    sessions.serve_through(frontend)
    pool = ThreadPoolExecutor(max_workers=args.frontend_threads,
                              thread_name_prefix="frontend")
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.tokens + 1
    kw = {}
    if cfg.family == "vlm":
        kw["extra_embeds"] = jnp.zeros((args.batch, cfg.frontend_seq,
                                        cfg.d_model), jnp.float32)
        max_len += cfg.frontend_seq
    if cfg.is_encdec:
        kw["enc_frames"] = jnp.zeros((args.batch, cfg.frontend_seq,
                                      cfg.d_model), jnp.float32)

    done, rid, t0 = 0, 1000.0, time.time()
    try:
        while done < args.requests:
            ids = []
            for _ in range(args.batch):
                rid += 1.0
                ids.append(rid)
            # admits fan out across the frontend threads; each admit is a
            # get+upsert pair through the batcher under the table lock
            list(pool.map(sessions.admit, ids))
            # KV-slot resolution for the decode batch rides the batched
            # lookup path (coalesced with any other serving traffic)
            slots, found = sessions.lookup_batch(ids)
            assert found.all(), "admitted sessions must resolve"
            prompts = rng.integers(
                0, cfg.vocab,
                (args.batch, args.prompt_len)).astype(np.int32)
            cache = MDL.make_cache(cfg, args.batch, max_len)
            batch = dict(tokens=jnp.asarray(prompts), **kw)
            logits, cache = prefill(params, batch, cache)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            for _ in range(args.tokens - 1):
                tok, logits, cache = decode(params, tok, cache)
            list(pool.map(sessions.evict, ids))
            done += args.batch
    finally:
        pool.shutdown(wait=True)
        stats = frontend.stats()
        frontend.close()
    dt = time.time() - t0
    print(f"[serve] {done} requests x {args.tokens} tokens in {dt:.1f}s "
          f"({done * args.tokens / dt:.1f} tok/s)")
    print(f"[serve] frontend: {stats['accepted_ops']} ops in "
          f"{stats['n_batches']} batches "
          f"(mean {stats['batch_ops_mean']:.1f} ops/batch, "
          f"shed {stats['shed_ops']})")


if __name__ == "__main__":
    main()
