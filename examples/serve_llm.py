"""Serving driver: batched requests against a small model with a DILI
session table on the admission/KV-slot path (Algorithms 7/8 in serving).

    PYTHONPATH=src python examples/serve_llm.py --requests 24 --tokens 16
"""
import argparse
import dataclasses
import os
import time

# the session table's index keys are f64; the model uses explicit dtypes
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import model as MDL
from repro.serve.sessions import SessionTable
from repro.train import step as STEP


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_config("granite-8b"), name="granite-serve", n_layers=4,
        d_model=256, n_heads=4, n_kv_heads=2, d_ff=512, vocab=512,
        head_dim=64, dtype="float32")
    params = MDL.init_params(jax.random.PRNGKey(0), cfg)
    prefill = jax.jit(STEP.make_prefill_step(cfg))
    decode = jax.jit(STEP.make_decode_step(cfg))

    sessions = SessionTable(n_slots=args.batch + 4)
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.tokens + 1

    t0 = time.time()
    done = 0
    req_id = 1000.0
    while done < args.requests:
        # admit a batch of sessions (DILI insert path)
        batch_ids = []
        for _ in range(args.batch):
            req_id += 1.0
            slot = sessions.admit(req_id)
            batch_ids.append(req_id)
        slots, found = sessions.lookup_batch(batch_ids)
        assert found.all()

        prompts = rng.integers(0, cfg.vocab,
                               (args.batch, args.prompt_len)).astype(np.int32)
        cache = MDL.make_cache(cfg, args.batch, max_len)
        logits, cache = prefill(params, dict(tokens=jnp.asarray(prompts)),
                                cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        outs = [np.asarray(tok)]
        for _ in range(args.tokens - 1):
            tok, logits, cache = decode(params, tok, cache)
            outs.append(np.asarray(tok))
        gen = np.concatenate(outs, axis=1)
        assert gen.shape == (args.batch, args.tokens)

        # evict (DILI delete path; slots recycled)
        for rid in batch_ids:
            sessions.evict(rid)
        done += args.batch
    dt = time.time() - t0
    total_toks = args.requests * args.tokens
    print(f"[serve] {done} requests, {total_toks} generated tokens in "
          f"{dt:.1f}s ({total_toks / dt:.0f} tok/s incl. prefill+sessions)")
    print(f"[serve] session-table stats: {sessions.dili.stats()}")


if __name__ == "__main__":
    main()
