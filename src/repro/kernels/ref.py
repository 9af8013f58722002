"""Pure-jnp oracle for the Pallas dili_search kernel.

Mirrors the kernel semantics exactly: f32 keys/models, mul-then-add slot
prediction, fixed `max_depth` unrolled traversal, dense (DILI-LO) leaves
bisected for the first key >= q, and only depth overflow flagged for the
wrapper's XLA fallback (see ops.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TAG_EMPTY, TAG_PAIR, TAG_CHILD = 0, 1, 2


def dili_search_ref(a, b, base, fo, dense, tag, key, val, root, queries,
                    max_depth: int):
    """Returns (vals, found, needs_fallback) for a batch of queries."""
    q = queries
    zi = (q * 0).astype(jnp.int32)
    n = zi + root
    done = zi > 0
    out = zi - 1
    found = zi > 0

    for _ in range(max_depth):
        an = a[n]
        bn = b[n]
        fon = fo[n]
        is_dense = dense[n] > 0
        pos = jnp.clip(jnp.floor(an + bn * q).astype(jnp.int32), 0, fon - 1)
        pos = jnp.where(is_dense, _lower_bound(key, base[n], fon, q), pos)
        s = base[n] + pos
        t = tag[s]
        sk = key[s]
        sv = val[s]
        active = ~done
        is_child = (t == TAG_CHILD) & ~is_dense & active
        hit = (t == TAG_PAIR) & (sk == q) & active
        out = jnp.where(hit, sv, out)
        found = found | hit
        n = jnp.where(is_child, sv, n)
        done = done | (active & ~is_child)

    fallback = ~done              # ran out of depth: let the wrapper recheck
    return out, found, fallback


def _lower_bound(key, base, fo, q):
    """First index in [0, fo) of each dense leaf whose key is >= q, clipped
    to the last slot (a plain 32-trip bisection, one lane per query)."""
    lo, hi = jnp.zeros_like(fo), fo
    for _ in range(32):
        mid = (lo + hi) // 2
        go = lo < hi
        below = key[base + jnp.minimum(mid, jnp.maximum(fo - 1, 0))] < q
        lo = jnp.where(go & below, mid + 1, lo)
        hi = jnp.where(go & ~below, mid, hi)
    return jnp.minimum(lo, jnp.maximum(fo - 1, 0))
