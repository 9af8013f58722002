"""Smoke test of the served index on a TPU, through the user's entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip range-partitioned path

One chip runs two phases:

  * pallas: the Pallas engine over the largest even-integer key set whose
    kernel tables fit the VMEM budget, so lookups take the compiled kernel;
    64K lookups (half hits, half misses) must equal the local engine's
    answers and `SortedOracle`'s bit for bit;
  * served: the local engine with background maintenance over 4M keys,
    behind `ServeFrontend`, driven open loop (ycsb_c, ycsb_a, then range
    scans) and journaled; the journal replays against `SortedOracle` with
    zero divergences, and no request, merge or warm executable may fail.

`--chips 4` runs only the sharded phase: the range-partitioned engine over
all four chips, with gather and a2a lookups, ranges, and upserts and
deletes before and after `flush()`, all compared with `SortedOracle`.

Everything runs in this one process (it holds the chip).  Without a TPU it
exits non-zero before any work.  The last stdout line is
`{"ok": true, "device": {"platform", "kind", "count"}}`; a failed check
raises instead, and that line is never printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ["JAX_ENABLE_X64"] = "1"     # f64 keys: must precede importing jax

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")]

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

from common import workload_universe                         # noqa: E402
from repro.api import IndexConfig, LearnedIndex, manual_merge_policy  # noqa: E402,E501
from repro.kernels.ops import ROUTE_XLA, VMEM_BUDGET_BYTES   # noqa: E402
from repro.workloads import SortedOracle                     # noqa: E402

#: the served phase's key count: the paper loads 200M keys, but the host
#: bulk load is Python (about a minute per million keys on one core)
N_KEYS = 4_000_000


class SmokeFailure(AssertionError):
    """A check of the smoke failed (raised, so the JSON line never prints)."""


def require(ok: bool, detail) -> None:
    if not ok:
        raise SmokeFailure(detail)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def hit_miss_queries(keys: np.ndarray, n: int, seed: int) -> np.ndarray:
    """n queries, half loaded keys and half absent odd integers, shuffled."""
    rng = np.random.default_rng(seed)
    hits = keys[rng.integers(0, len(keys), n // 2)]
    misses = 2.0 * rng.integers(0, len(keys), n - n // 2) + 1.0
    return rng.permutation(np.concatenate([hits, misses]))


def lookup_mismatches(got, want) -> int:
    """Lanes whose found flag or (found) value differ."""
    (gv, gf), (wv, wf) = got, want
    return int(np.sum((gf != wf) | (wf & (gv != wv))))


def range_mismatches(got, want) -> int:
    """Queries whose window (keys, vals or count) differs anywhere."""
    bad = np.zeros(len(want[2]), bool)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        bad |= (g != w).reshape(len(bad), -1).any(axis=1)
    return int(bad.sum())


# -- pallas: the compiled kernel ----------------------------------------------


def phase_pallas(budget: int = VMEM_BUDGET_BYTES, probe_n: int = 16384,
                 n_queries: int = 65536, batch: int = 8192,
                 seed: int = 1) -> dict:
    """Pallas engine at the largest key count whose kernel tables fit
    `budget`; its lookups must take the kernel route and match the local
    engine and the oracle on every lane."""
    def build(engine, n):
        return LearnedIndex.build(workload_universe(n), config=IndexConfig(
            engine=engine, vmem_budget_bytes=budget))

    # bytes per key fall as n grows (f32 placement packs big leaves dense):
    # rescale n by the last build's bytes per key until the tables fill
    # 95% of the budget, keeping the largest build that fits
    probe = build("pallas", probe_n)
    n = int(probe_n * budget / probe.stats()["table_bytes"] * 0.98)
    probe.close()
    ix = None
    for _ in range(4):
        t0 = time.perf_counter()
        cand = build("pallas", n)
        cand_s = time.perf_counter() - t0
        cst = cand.stats()
        if cst["kernel_eligible"] and (ix is None or n > len(keys)):
            if ix is not None:
                ix.close()
            ix, st, keys, build_s = cand, cst, workload_universe(n), cand_s
            if cst["table_bytes"] >= 0.95 * budget:
                break
        else:
            cand.close()
        n = int(n * budget / cst["table_bytes"] * 0.98)
    require(ix is not None, f"no key count fits the {budget}-byte budget")
    n = len(keys)
    log(f"pallas: n_keys={n} table_bytes={st['table_bytes']} "
        f"vmem_budget_bytes={budget} build_s={build_s:.2f}")
    local = build("local", n)
    oracle = SortedOracle(keys)
    q = hit_miss_queries(keys, n_queries, seed)
    bad_local = bad_oracle = 0
    t0 = time.perf_counter()
    for i in range(0, n_queries, batch):
        got = ix.lookup(q[i:i + batch])
        bad_local += lookup_mismatches(got, local.lookup(q[i:i + batch]))
        bad_oracle += lookup_mismatches(got, oracle.lookup(q[i:i + batch]))
    wall_s = time.perf_counter() - t0
    st = ix.stats()
    routes = st["kernel_routes"]
    ix.close()
    local.close()
    out = dict(n_keys=n, lookups=n_queries, mismatches_vs_local=bad_local,
               mismatches_vs_oracle=bad_oracle, routes=routes,
               recheck_patched_lanes=st["recheck_patched_lanes"])
    log(f"pallas: lookups={n_queries} mismatches_vs_local={bad_local} "
        f"mismatches_vs_oracle={bad_oracle} routes={routes} "
        f"recheck_patched_lanes={st['recheck_patched_lanes']} "
        f"peak_bytes_in_use={peak_bytes()}")
    log(f"pallas: smoke reading, not a metric: "
        f"{n_queries / wall_s:.0f} lookups/s with oracle checks interleaved")
    require(bad_local == 0 and bad_oracle == 0, out)
    require(routes.get(ROUTE_XLA, 0) == 0 and sum(routes.values()) > 0, out)
    # the recheck keeps answers exact whatever the kernel did; a kernel that
    # resolved less than 99% of the hit lanes itself is broken, not slow
    require(st["recheck_patched_lanes"] <= n_queries // 200, out)
    return out


# -- served: the main path ----------------------------------------------------


def phase_served(n_keys: int = N_KEYS, n_ops: int = 16384,
                 n_range_ops: int = 4096, rate: float = 5000.0,
                 n_clients: int = 4, req_ops: int = 16) -> dict:
    """The local engine with background maintenance, served open loop
    through `ServeFrontend` and checked by journal replay."""
    from run import journal_replay_check, serve_index, warm_serve_buckets
    from repro.serve import ServeConfig, ServeFrontend, open_loop
    from repro.workloads import PRESETS, WorkloadSpec, generate_stream

    keys = workload_universe(n_keys)
    t0 = time.perf_counter()
    ix = serve_index(keys, background=True, telemetry=True)
    build_s = time.perf_counter() - t0
    flatten_s = ix.metrics()["spans"]["merge.flatten"]["ms_max"] / 1e3
    log(f"served: n_keys={n_keys} build_s={build_s:.2f} "
        f"flatten_s={flatten_s:.2f} peak_bytes_in_use={peak_bytes()}")
    scfg = ServeConfig()
    warm_serve_buckets(ix, keys, scfg)
    fe = ServeFrontend(ix, scfg, journal=True)
    legs = [(PRESETS["ycsb_c"].scaled(n_ops=n_ops, batch_size=req_ops,
                                      seed=11)),
            (PRESETS["ycsb_a"].scaled(n_ops=n_ops, batch_size=req_ops,
                                      seed=12)),
            WorkloadSpec(name="scan", lookup=0.0, range_=1.0,
                         n_ops=n_range_ops, batch_size=req_ops,
                         max_hits=scfg.max_hits, seed=13)]
    served = shed = failed = 0
    for spec in legs:
        # ycsb_a only updates loaded keys, so every leg's stream can be
        # generated over the loaded key set
        rep = open_loop(fe, generate_stream(spec, keys), rate,
                        n_clients=n_clients, timeout_s=600.0)
        served += rep.done_ops
        shed += rep.shed_ops
        failed += rep.failed_ops
        log(f"served: {spec.name} ops={rep.n_ops} done={rep.done_ops} "
            f"shed={rep.shed_ops} failed={rep.failed_ops}; smoke reading, "
            f"not a metric: {rep.achieved_ops_per_s:.0f} ops/s at "
            f"{rate:.0f} offered")
    journal = fe.journal_batches()
    fe.close()
    deadline = time.perf_counter() + 600.0
    while ix.stats()["maint_queue_depth"] and time.perf_counter() < deadline:
        time.sleep(0.1)                       # let a running merge finish
    st, m = ix.stats(), ix.metrics()
    background_merges = sum(c for r, c in st["merge_reasons"].items()
                            if r != "flush")
    retraces = m["retrace"]["post_warmup_traces"]
    out = dict(n_keys=n_keys, served_ops=served, shed_ops=shed,
               failed_ops=failed, maint_errors=st["maint_errors"],
               maint_errors_counter=m["counters"]["maint.errors"],
               maint_degraded=st["maint_degraded"],
               merges=background_merges, retraces=retraces)
    log(f"served: ops_served={served} shed_ops={shed} failed_ops={failed} "
        f"merges={background_merges} maint_errors={st['maint_errors']} "
        f"maint_degraded={st['maint_degraded']} "
        f"post_warmup_retraces={retraces} "
        f"peak_bytes_in_use={peak_bytes()}")
    require(failed == 0, out)
    require(st["maint_errors"] == 0 and not st["maint_error_logs"], st)
    require(out["maint_errors_counter"] == 0 and not st["maint_degraded"], out)
    require(background_merges >= 1 and retraces == 0, out)
    t0 = time.perf_counter()
    check = journal_replay_check(ix, journal, keys, "chip_smoke,served")
    ix.close()
    out.update(divergences=check["n_divergences"],
               journal_batches=check["journal_batches"])
    log(f"served: replay divergences={check['n_divergences']} "
        f"journal_batches={check['journal_batches']} "
        f"checked_ops={check['checked_ops']} bit_exact={check['bit_exact']} "
        f"replay_s={time.perf_counter() - t0:.2f}")
    require(check["n_divergences"] == 0, check)
    return out


# -- sharded: four chips ------------------------------------------------------


def phase_sharded(n_keys: int = N_KEYS, n_chips: int = 4,
                  n_queries: int = 16384, n_ranges: int = 2048,
                  n_writes: int = 2048, seed: int = 2) -> dict:
    """The range-partitioned engine across `n_chips` devices: gather and
    a2a lookups and `max_hits=128` ranges, checked against the oracle
    before writes, with upserts and deletes pending, and after `flush()`."""
    from dataclasses import replace

    keys = workload_universe(n_keys)
    t0 = time.perf_counter()
    ix = LearnedIndex.build(keys, config=IndexConfig(
        engine="sharded", n_shards=n_chips, merge=manual_merge_policy()))
    st = ix.stats()
    log(f"sharded: n_keys={n_keys} n_shards={st['n_shards']} "
        f"device_ids={st['device_ids']} "
        f"build_s={time.perf_counter() - t0:.2f}")
    require(len(set(st["device_ids"])) == n_chips, st)
    oracle = SortedOracle(keys)
    rng = np.random.default_rng(seed)
    q = hit_miss_queries(keys, n_queries, seed)
    # 1..100 keys wide; the first few start just below a shard boundary
    lo = keys[rng.integers(0, len(keys), n_ranges)]
    cuts = [len(keys) * r // n_chips for r in range(1, n_chips)]
    lo[: len(cuts)] = keys[np.maximum(np.asarray(cuts, int) - 50, 0)]
    hi = lo + 2.0 * rng.integers(1, 101, n_ranges)
    engine = ix._engine
    results: dict = {}

    def check(stage: str) -> None:
        for strategy in ("gather", "a2a"):
            # both strategies read the same published shards: switch the
            # engine's read path in place instead of a second build
            engine.cfg = replace(engine.cfg, lookup_strategy=strategy)
            bad = lookup_mismatches(ix.lookup(q), oracle.lookup(q))
            results[f"{stage},{strategy}"] = bad
        bad = range_mismatches(ix.range(lo, hi, max_hits=128),
                               oracle.range(lo, hi, max_hits=128))
        results[f"{stage},range"] = bad
        log(f"sharded: {stage}: gather_mismatches="
            f"{results[stage + ',gather']} a2a_mismatches="
            f"{results[stage + ',a2a']} range_mismatches={bad} "
            f"lookups={n_queries} ranges={n_ranges}")

    check("built")
    upd = keys[rng.integers(0, len(keys), n_writes)]
    new = 2.0 * rng.integers(0, len(keys), n_writes) + 1.0
    up_k = np.concatenate([upd, new])
    up_v = np.arange(len(up_k), dtype=np.int64) + 10_000_000
    dead = keys[rng.integers(0, len(keys), n_writes // 2)]
    for idx in (ix, oracle):
        idx.upsert(up_k, up_v)
        idx.delete(dead)
    q = np.concatenate([q, up_k[: n_writes // 2], dead[: n_writes // 2]])
    check("pending")
    ix.flush()
    check("flushed")
    ix.close()
    log(f"sharded: peak_bytes_in_use={peak_bytes()}")
    require(not any(results.values()), results)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the four-chip sharded phase")
    ap.add_argument("--n-keys", type=int, default=N_KEYS,
                    help="key count of the served and sharded phases")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1
    from repro.compile_cache import cache_counts, enable_persistent_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_persistent_cache()}")
    if args.n_keys != N_KEYS:
        log(f"n_keys cut to {args.n_keys} (default {N_KEYS})")
    else:
        log(f"n_keys={N_KEYS}: cut from the paper's 200M, because the host "
            f"bulk load is Python (about a minute per million keys)")
    if args.chips == 4:
        phase_sharded(n_keys=args.n_keys, n_chips=4)
    else:
        phase_pallas()
        phase_served(n_keys=args.n_keys)
    log(f"compile cache: {cache_counts()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
