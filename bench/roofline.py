"""What one DILI lookup must read, and the chip's published peaks.

The count depends on the snapshot alone, not on which implementation
searches it (the XLA traversal or the Pallas kernel), so a rewrite of the
search cannot move the yardstick.  Per query, for each level visited, one
linear model (two key-width numbers) and one child id (4 bytes); at the
leaf, one slot (a key and an 8-byte value).  The levels come from the
tree's depth histogram: a walk of the snapshot's tables from the root,
counting the pairs held at each depth.
"""

from __future__ import annotations

import json
import os

import numpy as np

TAG_PAIR, TAG_CHILD = 1, 2
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "device_peaks.json")


def depth_histogram(tables) -> dict:
    """{depth: pairs held at that depth} (root = depth 1) from a mapping
    holding the tables `base`, `fo`, `tag`, `val` and `root`."""
    base = np.asarray(tables["base"], np.int64)
    fo = np.asarray(tables["fo"], np.int64)
    tag = np.asarray(tables["tag"]).astype(np.int64)
    val = np.asarray(tables["val"]).astype(np.int64)
    frontier = np.atleast_1d(np.asarray(tables["root"], np.int64))[:1]
    hist, depth = {}, 1
    while len(frontier):
        n = fo[frontier]
        start = np.repeat(base[frontier], n)
        slots = start + (np.arange(n.sum()) - np.repeat(np.cumsum(n) - n, n))
        t = tag[slots]
        pairs = int((t == TAG_PAIR).sum())
        if pairs:
            hist[depth] = pairs
        frontier = val[slots[t == TAG_CHILD]]
        depth += 1
    return hist


def bytes_per_query(tables) -> float:
    """Mean bytes a lookup of a loaded key must read, uniform over the
    loaded keys."""
    hist = depth_histogram(tables)
    kw = np.dtype(np.asarray(tables["key"][:1]).dtype).itemsize
    pairs = sum(hist.values())
    levels = sum(d * c for d, c in hist.items()) / pairs
    return levels * (2 * kw + 4) + (kw + 8)


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown device is an
    error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]
