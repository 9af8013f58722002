"""The plain reference and the comparison that decides `correct`.

`SortedReference` is a sorted key array and its value array: lookups
bisect it, ranges slice it, writes insert, overwrite or delete in place.
It shares no code with the program under test.

`check(window, journal, initial, final_items)` replays the served run:

  * the batcher's commit-order journal must hold exactly the requests
    that were answered, batch by batch: a write batch is its requests'
    payloads concatenated in each client's program order; a read batch,
    whose lanes all see one state, holds its requests' keys;
  * each client's requests must commit in its program order;
  * every request's answer must equal the reference's answer at the
    point of the commit order where its batch ran: what the coalescing,
    the facade's padding and slicing, the engine's search, the overlay
    and the merges published meanwhile all produced together;
  * every request accepted must have been answered;
  * the index's final `items()` must equal the reference's final state.

Each of these is a count with the limit 0.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def _last_wins(keys: np.ndarray, vals: np.ndarray):
    """Distinct keys of a write batch, each with its last value."""
    rk, rv = keys[::-1], vals[::-1]
    uk, first = np.unique(rk, return_index=True)
    return uk, rv[first]


class SortedReference:
    """Exact ordered map over float keys with int64 values."""

    def __init__(self, keys, vals, key_dtype=np.float64):
        self.dtype = np.dtype(key_dtype)
        k = np.asarray(keys, np.float64).astype(self.dtype)
        v = np.asarray(vals, np.int64)
        k, v = _last_wins(k, v)
        self.keys, self.vals = k, v

    def _cast(self, x) -> np.ndarray:
        return np.atleast_1d(np.asarray(x, np.float64)).astype(self.dtype)

    def lookup(self, q):
        q = self._cast(q)
        i = np.searchsorted(self.keys, q)
        ic = np.minimum(i, len(self.keys) - 1)
        found = (i < len(self.keys)) & (self.keys[ic] == q)
        return np.where(found, self.vals[ic], 0), found

    def range(self, lo, hi, max_hits: int):
        lo, hi = self._cast(lo), self._cast(hi)
        a = np.searchsorted(self.keys, lo, side="left")
        b = np.searchsorted(self.keys, hi, side="left")
        cnt = np.clip(b - a, 0, max_hits)
        pos = a[:, None] + np.arange(max_hits)[None, :]
        ok = np.arange(max_hits)[None, :] < cnt[:, None]
        pc = np.minimum(pos, len(self.keys) - 1)
        ks = np.where(ok, self.keys[pc].astype(np.float64), np.inf)
        vs = np.where(ok, self.vals[pc], -1)
        return ks, vs, cnt

    def upsert(self, keys, vals) -> None:
        k, v = _last_wins(self._cast(keys), np.asarray(vals, np.int64))
        i = np.searchsorted(self.keys, k)
        ic = np.minimum(i, len(self.keys) - 1)
        there = (i < len(self.keys)) & (self.keys[ic] == k)
        self.vals[i[there]] = v[there]
        if (~there).any():
            self.keys = np.insert(self.keys, i[~there], k[~there])
            self.vals = np.insert(self.vals, i[~there], v[~there])

    def delete(self, keys) -> None:
        k = np.unique(self._cast(keys))
        i = np.searchsorted(self.keys, k)
        ic = np.minimum(i, len(self.keys) - 1)
        there = (i < len(self.keys)) & (self.keys[ic] == k)
        self.keys = np.delete(self.keys, i[there])
        self.vals = np.delete(self.vals, i[there])

    def items(self):
        return self.keys.astype(np.float64), self.vals.copy()


@dataclass
class Verdict:
    """The counts compared, each against the limit 0."""
    wrong_answers: int = 0       # lanes whose answer differs
    unanswered: int = 0          # accepted requests never answered
    order_violations: int = 0    # journal not an in-order interleaving
    items_mismatch: int = 0      # final items() entries that differ
    answers_checked: int = 0

    LIMITS = {"wrong_answers": 0, "unanswered": 0, "order_violations": 0,
              "items_mismatch": 0}

    @property
    def correct(self) -> bool:
        return all(getattr(self, k) <= lim for k, lim in self.LIMITS.items())

    def checks(self) -> dict:
        return {k: {"value": getattr(self, k), "limit": lim}
                for k, lim in self.LIMITS.items()}


def _cols(op: str, x):
    """The payload columns of a request or a journal batch."""
    if op == "range":
        return (x.lo, x.hi)
    if op == "upsert":
        return (x.keys, x.vals)
    return (x.keys,)


def _same_multiset(a_cols, b_cols) -> bool:
    a = np.stack([np.asarray(c, np.float64) for c in a_cols], 1)
    b = np.stack([np.asarray(c, np.float64) for c in b_cols], 1)
    if a.shape != b.shape:
        return False
    return np.array_equal(a[np.lexsort(a.T[::-1])], b[np.lexsort(b.T[::-1])])


def _in_order(grp, batch) -> bool:
    """Is the write batch exactly its requests, each client's in program
    order?  Written values are unique per lane, so each request's place
    in the batch is unambiguous."""
    keys, vals = batch.keys, batch.vals
    at = {int(v): i for i, v in enumerate(vals)}
    last: dict[int, int] = {}
    covered = 0
    for s in sorted(grp, key=lambda s: (s.client, s.seq)):
        i = at.get(int(s.req.vals[0]), -1)
        m = s.req.n_ops
        if (i < 0 or not np.array_equal(keys[i:i + m], s.req.keys)
                or not np.array_equal(vals[i:i + m], s.req.vals)
                or i <= last.get(s.client, -1)):
            return False
        last[s.client] = i
        covered += m
    return covered == len(vals)


def _answer_mismatches(op: str, got, want) -> int:
    """Lanes whose answer differs (a lookup lane: found flag, or value
    where found; a range lane: any key, value or the count)."""
    if op == "lookup":
        (gv, gf), (wv, wf) = got, want
        gv, gf = np.asarray(gv), np.asarray(gf, bool)
        return int(np.sum((gf != wf) | (wf & (gv != wv))))
    gk, gv, gc = (np.asarray(x) for x in got)
    wk, wv, wc = want
    bad = (gc != wc) | (gk != wk).any(axis=1) | (gv != wv).any(axis=1)
    return int(bad.sum())


def check(window, journal, initial, final_items, max_hits: int = 128,
          key_dtype=np.float64) -> Verdict:
    """Replay the journal through the reference and compare (see module
    docstring).  `initial` is the loaded (keys, vals)."""
    v = Verdict()
    ref = SortedReference(*initial, key_dtype=key_dtype)
    done = [s for s in window.sent
            if s.handle.done and s.handle.error is None]
    v.unanswered = len(window.sent) - len(done)
    # the requests of one batch share its completion time, and the
    # batches complete in commit order
    groups: dict[float, list] = {}
    for s in done:
        groups.setdefault(s.handle.t_done, []).append(s)
    ordered = [groups[t] for t in sorted(groups)]
    v.order_violations += abs(len(ordered) - len(journal))
    last_seq: dict[int, int] = {}          # client -> its last seq so far
    for grp, batch in zip(ordered, journal):
        op = batch.op
        if any(s.req.op != op for s in grp):
            v.order_violations += 1
            continue
        # a batch is exactly its requests: for writes in each client's
        # program order; reads in one batch all see one state, so for them
        # the multiset is what counts
        ok = (_in_order(grp, batch) if op == "upsert" else
              _same_multiset([np.concatenate(c) for c in zip(
                  *(_cols(op, s.req) for s in grp))], _cols(op, batch)))
        v.order_violations += not ok
        # each client's requests commit in its program order
        for c in {s.client for s in grp}:
            seqs = [s.seq for s in grp if s.client == c]
            v.order_violations += min(seqs) <= last_seq.get(c, -1)
            last_seq[c] = max(seqs)
        if op in ("lookup", "range"):
            for s in grp:
                want = (ref.lookup(s.req.keys) if op == "lookup" else
                        ref.range(s.req.lo, s.req.hi, max_hits))
                v.wrong_answers += _answer_mismatches(op, s.handle.result,
                                                      want)
                v.answers_checked += s.req.n_ops
        elif op == "upsert":
            ref.upsert(batch.keys, batch.vals)
        else:
            ref.delete(batch.keys)
    rk, rv = ref.items()
    fk, fv = (np.asarray(x) for x in final_items)
    if len(fk) != len(rk):
        v.items_mismatch = abs(len(fk) - len(rk)) + int(
            np.sum(~np.isin(rk, fk)))
    else:
        v.items_mismatch = int(np.sum((fk != rk) | (fv != rv)))
    return v
