"""Shared fixtures.  x64 is enabled for the whole test session: the index
(key) paths need f64."""
import os
os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np
import pytest

from bench import keys as bench_keys


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)


def make_keys(dist: str, n: int, rng) -> np.ndarray:
    if dist == "logn":
        return np.unique(rng.lognormal(0, 1, n))
    if dist == "uniform":
        return np.unique(rng.uniform(0, 1e9, n))
    if dist == "fb":        # long-tail pareto (FB-id-like)
        return np.unique((rng.pareto(1.1, n) + 1) * 1e5)
    if dist == "wikits":    # near-sequential timestamps
        return np.unique(np.cumsum(rng.integers(1, 5, n)).astype(np.float64))
    raise ValueError(dist)


def ycsb_keys(shape: str, n: int, n_new: int):
    """The served cells' key set: YCSB's `n` records as `bench.keys` makes
    them ("hashed" or "ordered"), and the `n_new` keys YCSB inserts next,
    in insert order: the following record numbers, or their fnv64 hashes
    under insertorder=hashed, less any that float64 cannot tell from a
    loaded or an earlier key."""
    keys = bench_keys.make_keys(shape, n)
    recs = np.arange(n, n + 2 * n_new + 16)
    new = (bench_keys.fnvhash64(recs) if shape == "hashed"
           else recs).astype(np.float64)
    new = new[~np.isin(new, keys)]
    _, first = np.unique(new, return_index=True)
    return keys, new[np.sort(first)][:n_new]
