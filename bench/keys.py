"""Key sets of the benchmark's configurations, as YCSB's CoreWorkload
defines them for `recordcount` records numbered 0 .. n-1
(core/src/main/java/site/ycsb/workloads/CoreWorkload.java, `buildKeyName`,
and `Utils.fnvhash64`):

  hashed   insertorder=hashed, YCSB's default: the record number's
           FNV-1a-style 64-bit hash, `abs` of it as a signed long
  ordered  insertorder=ordered: the record numbers themselves

`make_keys(shape, n)` returns n sorted, distinct float64 keys.  The key
set is the source's and depends on n alone; the run's seed draws the
requests over it.  A hash that two records share once rounded to a
float64 is dropped, and the next record number takes its place.
"""

from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """YCSB's `Utils.fnvhash64` over int64 record numbers: eight octets,
    low first, each xored in and multiplied by the prime (mod 2^64), and
    `Math.abs` of the result read as a signed long."""
    v = np.asarray(vals, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def _hashed(n: int) -> np.ndarray:
    out = np.empty(0)
    start = 0
    while len(out) < n:
        need = n - len(out)
        fresh = fnvhash64(np.arange(start, start + need)).astype(np.float64)
        out = np.unique(np.concatenate([out, fresh]))
        start += need
    return out


def _ordered(n: int) -> np.ndarray:
    return np.arange(n, dtype=np.float64)


SHAPES = {"hashed": _hashed, "ordered": _ordered}


def make_keys(shape: str, n: int) -> np.ndarray:
    if shape not in SHAPES:
        raise ValueError(f"unknown key shape {shape!r}; known: "
                         f"{sorted(SHAPES)}")
    return SHAPES[shape](int(n))
