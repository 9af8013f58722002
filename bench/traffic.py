"""The one traffic generator: turns a mix file's parameters into requests.

A mix (`bench/traffic/<name>.json`) holds only data:

  ops               shares of "read", "update", "insert" and "scan"
  keys_per_request  keys (or scans) one request carries
  popularity        {"dist": "uniform" | "zipfian" | "latest",
                     "theta": 0.99} -- zipfian is YCSB's scrambled zipfian
                     (hot keys spread over the key space); latest is
                     zipfian over recency, newest first
  scan_len          [lo, hi]: scan lengths, uniform, in keys (scan only)
  loop              "open": a fixed schedule at `rate_ops_per_s`, dealt
                    round-robin to `clients` threads; "closed": `clients`
                    callers, each with one request in flight
  serve             optional `repro.serve.ServeConfig` fields

Open loop: every seed gets the same number of requests of each op type,
in another order, so seeds change which keys are touched and not how much
work there is.  Reads, updates and scans touch loaded keys; inserts add
new keys between two loaded ones; written values are unique per lane, so
every acknowledged write can be told apart from every other.

Popularity samplers are copies of `repro.workloads.distributions`
(YCSB's ZipfianGenerator and its multiplicative-hash scramble).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

OPS = ("read", "update", "insert", "scan")
#: first written value; written values count up from here (int32-safe)
VAL_BASE = 1_000_000_000


@dataclass(frozen=True)
class Req:
    """One request: a serve op and its payload arrays."""
    op: str                       # lookup | upsert | range
    keys: np.ndarray | None = None
    vals: np.ndarray | None = None
    lo: np.ndarray | None = None
    hi: np.ndarray | None = None

    @property
    def n_ops(self) -> int:
        return len(self.lo) if self.op == "range" else len(self.keys)

    def payload(self) -> dict:
        if self.op == "range":
            return dict(lo=self.lo, hi=self.hi)
        if self.op == "upsert":
            return dict(keys=self.keys, vals=self.vals)
        return dict(keys=self.keys)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))


def zipfian_ranks(rng, n: int, size: int, theta: float,
                  zetan: float) -> np.ndarray:
    """Ranks in [0, n) with P(r) proportional to 1/(r+1)^theta (YCSB's
    ZipfianGenerator, vectorized)."""
    if n <= 1:
        return np.zeros(size, np.int64)
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - (1 + 0.5 ** theta)
                                                / zetan)
    u = rng.random(size)
    uz = u * zetan
    ranks = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1,
                                           ranks))
    return np.clip(ranks, 0, n - 1)


def scatter(ranks: np.ndarray, n: int) -> np.ndarray:
    """Popularity rank -> key position (Knuth's multiplicative hash)."""
    return (ranks.astype(np.uint64) * np.uint64(2654435761)
            % np.uint64(n)).astype(np.int64)


class Traffic:
    """Requests of one mix over one loaded key set, from one seed."""

    def __init__(self, mix: dict, keys: np.ndarray, seed: int):
        self.mix = mix
        self.keys = np.asarray(keys, np.float64)
        self.seed = int(seed)
        shares = {op: float(s) for op, s in mix["ops"].items()}
        unknown = set(shares) - set(OPS)
        if unknown or not shares or min(shares.values()) < 0:
            raise ValueError(f"mix {mix.get('name')}: bad ops {mix['ops']}")
        total = sum(shares.values())
        self.shares = {op: s / total for op, s in shares.items() if s > 0}
        self.kpr = int(mix["keys_per_request"])
        pop = mix.get("popularity", {"dist": "uniform"})
        self.dist = pop["dist"]
        if self.dist not in ("uniform", "zipfian", "latest"):
            raise ValueError(f"unknown popularity {self.dist!r}")
        self.theta = float(pop.get("theta", 0.99))
        self._zetan = (zeta(len(self.keys), self.theta)
                       if self.dist != "uniform" else 0.0)
        self.scan_len = tuple(mix.get("scan_len", (1, 100)))
        self._next_val = VAL_BASE
        self._val_lock = threading.Lock()      # closed-loop callers share it

    @property
    def writes(self) -> bool:
        return bool({"update", "insert"} & set(self.shares))

    @property
    def scans(self) -> bool:
        return "scan" in self.shares

    # -- sampling -------------------------------------------------------------

    def _positions(self, rng, size: int, n_new_before=None,
                   new_keys=None) -> np.ndarray:
        """Keys chosen by popularity; for "latest", rank 0 is the newest
        key: the inserts made so far, newest first, then the loaded keys
        from the top down."""
        n = len(self.keys)
        if self.dist == "uniform":
            return self.keys[rng.integers(0, n, size)]
        ranks = zipfian_ranks(rng, n, size, self.theta, self._zetan)
        if self.dist == "zipfian":
            return self.keys[scatter(ranks, n)]
        m = (np.zeros(size, np.int64) if n_new_before is None
             else n_new_before)
        out = self.keys[np.clip(n - 1 - (ranks - m), 0, n - 1)]
        recent = ranks < m
        if recent.any():
            out[recent] = new_keys[(m - 1 - ranks)[recent]]
        return out

    def _vals(self, size: int) -> np.ndarray:
        with self._val_lock:
            start = self._next_val
            self._next_val += size
        return np.arange(start, start + size, dtype=np.int64)

    def _scan_bounds(self, rng, lo: np.ndarray):
        n = len(self.keys)
        length = rng.integers(self.scan_len[0], self.scan_len[1] + 1,
                              len(lo))
        end = np.searchsorted(self.keys, lo) + length
        hi = np.where(end < n, self.keys[np.minimum(end, n - 1)],
                      self.keys[-1] + 1.0)
        return lo, hi

    def _new_keys(self, rng, size: int) -> np.ndarray:
        """`size` distinct keys absent from the loaded set: midpoints of
        distinct gaps between adjacent loaded keys."""
        gaps = rng.choice(len(self.keys) - 1, size, replace=False)
        return 0.5 * (self.keys[gaps] + self.keys[gaps + 1])

    # -- open loop ------------------------------------------------------------

    def open_loop(self, seconds: float) -> list[Req]:
        """The whole window's requests in schedule order: the same count
        of each op type for every seed."""
        rate = float(self.mix["rate_ops_per_s"])
        n_req = max(1, int(round(rate * seconds / self.kpr)))
        rng = np.random.default_rng([self.seed, 1])
        counts = {op: int(np.floor(s * n_req))
                  for op, s in self.shares.items()}
        top = max(self.shares, key=self.shares.get)
        counts[top] += n_req - sum(counts.values())
        order = rng.permutation(np.concatenate(
            [np.full(c, OPS.index(op)) for op, c in counts.items()]))
        k = self.kpr
        is_ins = order == OPS.index("insert")
        new_keys = self._new_keys(rng, int(is_ins.sum()) * k)
        # inserts issued before each request (for "latest" popularity)
        new_before = np.repeat((np.cumsum(is_ins) - is_ins) * k, k)
        picked = self._positions(rng, n_req * k, new_before, new_keys)
        reqs: list[Req] = []
        ins = 0
        for i, code in enumerate(order):
            op = OPS[code]
            ks = picked[i * k:(i + 1) * k]
            if op == "read":
                reqs.append(Req("lookup", keys=ks))
            elif op == "update":
                reqs.append(Req("upsert", keys=ks, vals=self._vals(k)))
            elif op == "insert":
                reqs.append(Req("upsert", keys=new_keys[ins:ins + k],
                                vals=self._vals(k)))
                ins += k
            else:
                lo, hi = self._scan_bounds(rng, ks)
                reqs.append(Req("range", lo=lo, hi=hi))
        return reqs

    # -- closed loop ----------------------------------------------------------

    def caller_rng(self, caller: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, 2, caller])

    def next_request(self, rng) -> Req:
        """One closed-loop request (reads, updates and scans only)."""
        ops = list(self.shares)
        op = ops[int(rng.choice(len(ops), p=[self.shares[o] for o in ops]))
                 ] if len(ops) > 1 else ops[0]
        ks = self._positions(rng, self.kpr)
        if op == "read":
            return Req("lookup", keys=ks)
        if op == "update":
            return Req("upsert", keys=ks, vals=self._vals(self.kpr))
        if op == "scan":
            lo, hi = self._scan_bounds(rng, ks)
            return Req("range", lo=lo, hi=hi)
        raise ValueError("closed-loop mixes do not insert")
