"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus `#` section headers).
Scaled to BENCH_N_KEYS (default 300k; the paper's 200M is one env var away).
Lookup wall-times are CPU-JAX batched timings — the relative ordering is the
claim under test; the TPU roofline story lives in bench/roofline.py.

Cost model (since PR 2, DESIGN.md section 9): point lookups are depth-exact —
the traversal trip count is the snapshot's true `max_depth` with batch-
convergence early exit, never a fixed worst-case scan — and range queries
bisect the flatten()-time key-sorted pair table, O(log n + max_hits) per
query instead of the old O(n_slots) global slot-table mask-scan.  So lookup
cost scales with tree height and range cost with hits, not with table size.

``--json PATH`` additionally writes every row machine-readably;
``--pr2-json`` emits BENCH_PR2.json — the hot-path trajectory artifact
comparing against benchmarks/baseline_pre_pr2.json (captured on the pre-PR-2
tree with the same datasets/scales), extended since the api redesign with
facade sections measured through `repro.api.LearnedIndex` on the engine
selected by ``--engine {local,pallas,sharded}``.  Every section carries its
own ``n_keys`` stamp, and ``--pr2-extend`` merges a run at a DIFFERENT
scale (e.g. BENCH_N_KEYS=10000000 with ``--scale`` and ``--workload``)
into the existing artifact under ``@n=<scale>``-suffixed keys, leaving the
original sections byte-identical.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")   # BEFORE importing jax
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from common import (DATASETS, N_QUERIES, N_WORKLOAD_BATCH, N_WORKLOAD_OPS,
                    baseline_for, csv_row, dataset, dili_for, index_for,
                    queries_for, time_fn, workload_universe)

from repro.api import DeviceSnapshot                    # noqa: E402
from repro.core import search as S                      # noqa: E402
from repro.core.baselines import ALL_BASELINES          # noqa: E402
from repro.core.bu_tree import build_bu_tree, bu_search  # noqa: E402
from repro.core.dili import bulk_load                   # noqa: E402
from repro.core.flat import flatten                     # noqa: E402

# engine under test for the facade sections / --pr2-json (set by --engine)
ENGINE = "local"

# --metrics-json: when set, workload runs build with telemetry enabled and
# their `LearnedIndex.metrics()` snapshots collect here, one per section
METRICS_JSON = ""
METRICS_SECTIONS: dict = {}

# --trace-json: when set, the first --serve latency leg runs with causal
# tracing armed and dumps the Chrome-trace-event JSON here (Perfetto-viewable)
TRACE_JSON = ""


def _metrics_section(m: dict, n_keys: int) -> dict:
    """Stamp a `LearnedIndex.metrics()` snapshot with the section's key
    scale, mirroring the n_keys stamp every BENCH_PR2.json section
    carries (the snapshot already self-describes via its `schema` key)."""
    return {"n_keys": n_keys, **m}


def _dili_lookup_time(name: str, **kw) -> tuple[float, dict]:
    keys, d, f, idx = dili_for(name, **kw)
    q = jnp.asarray(queries_for(name))
    # serving configuration: depth-exact from the snapshot + early exit
    t = time_fn(lambda q: S.search_batch(idx, q, early_exit=True), q)
    v, fnd, nodes, probes = S.search_batch(idx, q, with_stats=True)
    assert bool(np.asarray(fnd).all())
    return t, dict(nodes=float(np.asarray(nodes).mean()),
                   probes=float(np.asarray(probes).mean()),
                   stats=d.stats())


def table4_lookup():
    """Table 4: lookup time of all methods after bulk loading."""
    print("# Table 4: lookup ns/query (batched CPU-JAX, scaled datasets)")
    for name in DATASETS:
        t, _ = _dili_lookup_time(name)
        csv_row(f"table4,{name},DILI", t / N_QUERIES * 1e9)
        tlo, _ = _dili_lookup_time(name, local_optimized=False)
        csv_row(f"table4,{name},DILI-LO", tlo / N_QUERIES * 1e9)
        q = jnp.asarray(queries_for(name))
        for B in ALL_BASELINES:
            st, dev = baseline_for(B, name)
            t = time_fn(lambda q: B.lookup(dev, q), q)
            csv_row(f"table4,{name},{B.name}", t / N_QUERIES * 1e9)


def table5_access():
    """Table 5 proxy: memory touches per query (nodes+slots gathered) —
    the TPU analogue of LL-cache misses."""
    print("# Table 5: memory touches per query")
    for name in DATASETS:
        _, st_ = _dili_lookup_time(name)
        csv_row(f"table5,{name},DILI", st_["nodes"] + st_["probes"])
        q = jnp.asarray(queries_for(name))
        for B in ALL_BASELINES:
            stb, dev = baseline_for(B, name)
            _, _, pr = B.lookup(dev, q)
            csv_row(f"table5,{name},{B.name}",
                    float(np.asarray(pr).mean()))


def table6_stats():
    """Table 6: DILI height stats + conflicts per 1K keys."""
    print("# Table 6: DILI construction statistics")
    for name in DATASETS:
        keys, d, f, idx = dili_for(name)
        s = d.stats()
        csv_row(f"table6,{name},min_h", s["min_height"])
        csv_row(f"table6,{name},max_h", s["max_height"])
        csv_row(f"table6,{name},avg_h", s["avg_height"])
        csv_row(f"table6,{name},conflicts_per_1k",
                1000.0 * s["conflicts"] / len(keys))


def fig6_memory_range():
    """Fig. 6: index sizes + short range queries (<=100 keys)."""
    print("# Fig 6a: index bytes per key")
    for name in DATASETS:
        keys, d, f, idx = dili_for(name)
        csv_row(f"fig6a,{name},DILI", f.nbytes() / len(keys))
        keys, d2, f2, _ = dili_for(name, local_optimized=False)
        csv_row(f"fig6a,{name},DILI-LO", f2.nbytes() / len(keys))
        for B in ALL_BASELINES:
            st, dev = baseline_for(B, name)
            if B.name == "LIPP":
                nb = st["flat"].nbytes()
            else:
                nb = sum(v.nbytes for v in st.values()
                         if isinstance(v, np.ndarray))
            csv_row(f"fig6a,{name},{B.name}", nb / len(keys))
    print("# Fig 6b: range query us/query (100-key ranges)")
    for name in DATASETS:
        keys, d, f, idx = dili_for(name)
        rng = np.random.default_rng(3)
        starts = rng.integers(0, len(keys) - 101, 512)
        lo = jnp.asarray(keys[starts])
        hi = jnp.asarray(keys[starts + 100])
        t = time_fn(lambda lo, hi: S.range_query_batch(idx, lo, hi,
                                                       max_hits=128), lo, hi)
        csv_row(f"fig6b,{name},DILI", t / 512 * 1e6)


def fig7_workloads():
    """Fig. 7: read-only/read-heavy/write-heavy/write-only throughput."""
    print("# Fig 7: workload throughput (us/op; derived=ops/s)")
    import time as _t
    for name in DATASETS:
        keys = dataset(name)
        half = keys[::2]
        other = np.setdiff1d(keys, half)
        rng = np.random.default_rng(4)
        for wl, n_q, n_i in (("read_only", 20000, 0),
                             ("read_heavy", 20000, 10000),
                             ("write_heavy", 10000, 20000),
                             ("write_only", 0, 20000)):
            d = bulk_load(half, sample_stride=4)
            qs = half[rng.integers(0, len(half), max(n_q, 1))]
            ins = other[rng.integers(0, len(other), max(n_i, 1))]
            t0 = _t.perf_counter()
            qi = ii = 0
            for k in range(n_q + n_i):
                if (k % 3 == 2 or qi >= n_q) and ii < n_i:
                    d.insert(float(ins[ii % len(ins)]), k)
                    ii += 1
                elif qi < n_q:
                    d.search(float(qs[qi]))
                    qi += 1
            dt = _t.perf_counter() - t0
            csv_row(f"fig7,{name},{wl}", 1e6 * dt / (n_q + n_i),
                    f"{(n_q + n_i) / dt:.0f} ops/s")


def fig8_deletions():
    """Fig. 8: read-heavy / deletion-heavy workloads with deletes."""
    print("# Fig 8: deletion workloads (us/op; derived=ops/s)")
    import time as _t
    for name in DATASETS:
        keys = dataset(name)
        rng = np.random.default_rng(5)
        for wl, n_q, n_d in (("read_heavy", 20000, 10000),
                             ("delete_heavy", 10000, 20000)):
            d = bulk_load(keys, sample_stride=4)
            dels = rng.permutation(keys)[:n_d]
            qs = keys[rng.integers(0, len(keys), n_q)]
            t0 = _t.perf_counter()
            qi = di = 0
            for k in range(n_q + n_d):
                if (k % 3 == 2 or qi >= n_q) and di < n_d:
                    d.delete(float(dels[di]))
                    di += 1
                elif qi < n_q:
                    d.search(float(qs[qi]))
                    qi += 1
            dt = _t.perf_counter() - t0
            csv_row(f"fig8,{name},{wl}", 1e6 * dt / (n_q + n_d),
                    f"{(n_q + n_d) / dt:.0f} ops/s")


def table78_hyperparams():
    """Tables 7/8: rho and lambda sweeps."""
    print("# Table 7: rho sweep")
    from repro.core.bu_tree import CostModel
    name = DATASETS[0]
    keys = dataset(name)
    q = jnp.asarray(queries_for(name))
    for rho in (0.05, 0.1, 0.2, 0.5):
        d = bulk_load(keys, cm=CostModel(rho=rho), sample_stride=4)
        f = flatten(d)
        idx = DeviceSnapshot.from_flat(f)
        t = time_fn(lambda q: S.search_batch(idx, q, early_exit=True), q)
        s = d.stats()
        csv_row(f"table7,rho={rho}", t / N_QUERIES * 1e9,
                f"avg_h={s['avg_height']:.2f};bytes/key="
                f"{f.nbytes() / len(keys):.1f}")
    print("# Table 8: lambda sweep")
    import time as _t
    half = keys[::2]
    other = np.setdiff1d(keys, half)[:30000]
    for lam in (1.5, 2.0, 4.0, 8.0):
        d = bulk_load(half, lam=lam, sample_stride=4)
        t0 = _t.perf_counter()
        for j, k in enumerate(other):
            d.insert(float(k), j)
        t_ins = (_t.perf_counter() - t0) / len(other)
        f = flatten(d)
        idx = DeviceSnapshot.from_flat(f)
        t = time_fn(lambda q: S.search_batch(idx, q, early_exit=True), q)
        s = d.stats()
        csv_row(f"table8,lambda={lam}", t / N_QUERIES * 1e9,
                f"ins_us={t_ins * 1e6:.1f};avg_h={s['avg_height']:.2f};"
                f"adj={s['adjustments']}")


def table9_breakdown():
    """Table 9: step-1/step-2 breakdown, DILI vs BU-Tree."""
    print("# Table 9: search step breakdown (probe counts)")
    for name in DATASETS:
        keys, d, f, idx = dili_for(name)
        rng = np.random.default_rng(6)
        picks = keys[rng.integers(0, len(keys), 400)]
        n1 = n2 = 0
        for k in picks:
            _, nodes, probes = d.search_stats(float(k))
            n1 += nodes
            n2 += probes
        csv_row(f"table9,{name},DILI", 0.0,
                f"step1_nodes={n1 / 400:.2f};step2_probes={n2 / 400:.2f}")
        bu = build_bu_tree(keys, sample_stride=4)
        nn = pp = 0
        for k in picks:
            _, nodes, probes = bu_search(bu, keys, float(k))
            nn += nodes
            pp += probes
        csv_row(f"table9,{name},BU-Tree", 0.0,
                f"step1_nodes={nn / 400:.2f};step2_probes={pp / 400:.2f}")


def table10_12_13_appendix():
    """Appendix: memory under write-heavy (T10), adjustment ablation (T12),
    sampled construction (T13)."""
    print("# Tables 10/12/13 (appendix)")
    import time as _t
    for name in DATASETS[:2]:
        keys = dataset(name)
        half = keys[::2]
        other = np.setdiff1d(keys, half)[:40000]
        d = bulk_load(half, sample_stride=4)
        before = d.stats()["memory_bytes"]
        for j, k in enumerate(other):
            d.insert(float(k), j)
        after = d.stats()["memory_bytes"]
        csv_row(f"table10,{name}", 0.0, f"before={before};after={after}")
        # T12: adjustments off (lambda = inf) vs on
        d2 = bulk_load(half, lam=1e18, sample_stride=4)
        t0 = _t.perf_counter()
        for j, k in enumerate(other):
            d2.insert(float(k), j)
        t_noadj = (_t.perf_counter() - t0) / len(other)
        s2 = d2.stats()
        csv_row(f"table12,{name},DILI-AD", t_noadj * 1e6,
                f"avg_h={s2['avg_height']:.2f}")
        d3 = bulk_load(half, sample_stride=4)
        t0 = _t.perf_counter()
        for j, k in enumerate(other):
            d3.insert(float(k), j)
        t_adj = (_t.perf_counter() - t0) / len(other)
        s3 = d3.stats()
        csv_row(f"table12,{name},DILI", t_adj * 1e6,
                f"avg_h={s3['avg_height']:.2f};adj={s3['adjustments']}")
        # T13: sampled construction
        t0 = _t.perf_counter()
        bulk_load(keys, sample_stride=1)
        t_full = _t.perf_counter() - t0
        t0 = _t.perf_counter()
        bulk_load(keys, sample_stride=4)
        t_samp = _t.perf_counter() - t0
        csv_row(f"table13,{name}", 0.0,
                f"build_full_s={t_full:.1f};build_sampled_s={t_samp:.1f}")


def fig9_scale():
    """Fig. 9a: lookup cost vs cardinality."""
    print("# Fig 9a: scalability (ns/query vs n)")
    from repro.data.datasets import generate
    rng = np.random.default_rng(8)
    for n in (50000, 100000, 200000, 400000):
        keys = generate("fb", n, seed=42)
        d = bulk_load(keys, sample_stride=4)
        f = flatten(d)
        idx = DeviceSnapshot.from_flat(f)
        q = jnp.asarray(keys[rng.integers(0, n, N_QUERIES)])
        t = time_fn(lambda q: S.search_batch(idx, q, early_exit=True), q)
        csv_row(f"fig9a,n={n}", t / N_QUERIES * 1e9)


def fig10_shift():
    """Fig. 9b/10: distribution shift / skewed writes."""
    print("# Fig 10: skewed inserts into an FB-built index")
    import time as _t
    fb = dataset("fb")
    logn = dataset("logn")
    span = fb[-1] - fb[0]
    shifted = fb[0] + (logn - logn[0]) / (logn[-1] - logn[0]) * span * 0.1
    shifted = np.setdiff1d(np.unique(shifted), fb)[:30000]
    d = bulk_load(fb, sample_stride=4)
    h0 = d.stats()["avg_height"]
    t0 = _t.perf_counter()
    for j, k in enumerate(shifted):
        d.insert(float(k), j)
    dt = (_t.perf_counter() - t0) / len(shifted)
    s = d.stats()
    csv_row("fig10,fb<-logn,insert_us", dt * 1e6,
            f"avg_h:{h0:.2f}->{s['avg_height']:.2f};adj={s['adjustments']}")


def online_mixed():
    """Mixed read/write workloads through the online-update subsystem:
    fused snapshot+overlay lookups, overlay writes, merge-policy publishes.
    Reports lookups/s, writes/s, and publish stalls (merge count + wall s)."""
    print("# online: mixed read/write (lookup/insert/delete) workloads")
    import time as _t
    from repro.online import MergePolicy, OnlineIndex
    for name in DATASETS:
        keys = dataset(name)
        half = keys[::2]
        other = np.setdiff1d(keys, half)
        rng = np.random.default_rng(12)
        for wl, read_frac in (("95r5w", 0.95), ("50r50w", 0.50)):
            oi = OnlineIndex(half, sample_stride=4, overlay_cap=8192,
                             policy=MergePolicy(max_fill=0.5,
                                                max_writes=16384))
            B, n_rounds = 4096, 16
            n_reads = n_writes = 0
            t_read = t_write = 0.0
            inserted: list = []
            wi = 0
            # warmup: trace/compile the fused lookup outside the timed window
            oi.lookup(jnp.asarray(half[:B]))
            for _ in range(n_rounds):
                q = jnp.asarray(half[rng.integers(0, len(half), B)])
                t0 = _t.perf_counter()
                v, f = oi.lookup(q)
                t_read += _t.perf_counter() - t0
                n_reads += B
                nw = int(round(B * (1 - read_frac) / read_frac))
                ups = other[wi % len(other): wi % len(other) + (2 * nw) // 3]
                wi += len(ups)
                dels = inserted[: nw - len(ups)]
                inserted = inserted[len(dels):]
                t0 = _t.perf_counter()
                if len(ups):
                    oi.upsert_batch(ups, 1_000_000 + np.arange(len(ups)))
                    inserted.extend(ups)
                if len(dels):
                    oi.delete_batch(np.asarray(dels))
                t_write += _t.perf_counter() - t0
                n_writes += len(ups) + len(dels)
            stall_s = sum(st.publish_s for st in oi.store.history[1:])
            csv_row(f"online,{name},{wl},lookups_per_s",
                    n_reads / max(t_read, 1e-9))
            csv_row(f"online,{name},{wl},writes_per_s",
                    n_writes / max(t_write, 1e-9))
            csv_row(f"online,{name},{wl},publish_stalls", oi.n_merges,
                    f"stall_s={stall_s:.3f};epochs={oi.epoch};"
                    f"reasons={dict(oi.merge_reasons)}")


def kernel_bench():
    """Pallas kernel (interpret) vs pure-XLA batched search + bytes/query."""
    print("# kernel: dili_search")
    from repro.kernels import ops as K
    from repro.core import search as S2
    name = DATASETS[0]
    keys = dataset(name)[:200000]
    d, keys32 = K.build_f32_index(keys)
    f = flatten(d)
    arrs = K.kernel_arrays(f)
    rng = np.random.default_rng(9)
    q = jnp.asarray(keys32[rng.integers(0, len(keys32), 16384)], jnp.float32)
    t = time_fn(lambda q: K.dili_search(arrs, q), q)
    csv_row("kernel,pallas_interpret", t / 16384 * 1e9,
            f"table_bytes={K.table_bytes(arrs)}")
    idx = K._as_search_idx(arrs)
    # depth resolves from the snapshot's own max_depth entry — no threading
    t2 = time_fn(lambda q: S2.search_batch(idx, q, early_exit=True), q)
    csv_row("kernel,xla_f32", t2 / 16384 * 1e9)
    # roofline: bytes/query on the device path (node+slot rows touched)
    v, fnd, nodes, probes = S2.search_batch(idx, q, with_stats=True)
    node_row, slot_row = 17, 9      # f32 snapshot row sizes
    bpq = float(np.asarray(nodes).mean()) * node_row \
        + float(np.asarray(probes).mean()) * slot_row
    csv_row("kernel,bytes_per_query", bpq,
            "v5e HBM roofline: 819e9/bytes_per_query lookups/s/chip")


def _facade_measure(name: str) -> tuple[float, float]:
    """One measurement recipe for the facade serving path (shared by
    facade_bench and the BENCH_PR2.json facade sections so the two can
    never drift): lookup ns/query over the standard query draw, and range
    us/query over 512 100-key windows, through `LearnedIndex` on ENGINE.
    Numbers include the host<->device boundary the facade owns."""
    ix = index_for(name, ENGINE)
    keys = dataset(name)
    q = queries_for(name)
    t = time_fn(lambda: ix.lookup(q))
    v, f = ix.lookup(q[:4096])
    assert bool(f.all()), (ENGINE, name)
    rng = np.random.default_rng(3)
    starts = rng.integers(0, len(keys) - 101, 512)
    tr = time_fn(lambda: ix.range(keys[starts], keys[starts + 100],
                                  max_hits=128))
    return t / N_QUERIES * 1e9, tr / 512 * 1e6


def facade_bench():
    """LearnedIndex end-to-end on the engine selected by --engine."""
    print(f"# facade: LearnedIndex on the '{ENGINE}' engine")
    for name in DATASETS:
        lookup_ns, range_us = _facade_measure(name)
        csv_row(f"facade,{ENGINE},{name},lookup_ns", lookup_ns,
                f"max_depth={index_for(name, ENGINE).stats()['max_depth']}")
        csv_row(f"facade,{ENGINE},{name},range_us", range_us)


def _maint_config(mode: str):
    from repro.api import MaintenanceConfig
    if mode == "off":
        return None
    if mode == "norecluster":
        # incremental maintenance with locality re-clustering disabled —
        # the ablation leg of --maintenance recluster-compare
        return MaintenanceConfig(recluster=False)
    return MaintenanceConfig(background=(mode == "background"))


def _latency_percentiles(timings: list[dict]) -> dict:
    """merge/publish wall-time percentiles (ms) over the run's merges, via
    the repo's ONE percentile recipe (`repro.obs.latency_summary`) — same
    keys/method as the runner's `latency_ms` and `metrics()` histograms."""
    from repro.obs import latency_summary
    if not timings:
        return dict(n_publishes=0)
    out: dict = dict(n_publishes=len(timings))
    for field in ("merge_s", "publish_s"):
        out.update(latency_summary((t[field] for t in timings),
                                   prefix=field[:-2]))  # merge_s -> merge
    out["dirty_row_fraction_mean"] = float(
        np.mean([t["dirty_frac"] for t in timings]))
    return out


def workload_bench(preset: str, maint_mode: str) -> dict:
    """YCSB-style mixed workload through the facade on ENGINE, oracle-
    checked batch by batch (any divergence raises -> the job fails).

    Returns BENCH_PR2.json-schema sections keyed `workload,<preset>`
    (plus `,bg` for background mode) so ``--workload X --pr2-json`` lands
    mixed-workload throughput AND merge/publish latency percentiles in
    the existing trajectory artifact.  `maint_mode` "compare" runs the
    preset twice — full-flatten baseline vs incremental maintenance — so
    the artifact records the publish-latency delta the maintenance
    subsystem buys.  Sized by BENCH_WORKLOAD_OPS / BENCH_WORKLOAD_BATCH;
    keys are the integer workload universe (see common.workload_universe),
    NOT the float datasets — popularity shape, not key shape, is what a
    mixed workload measures, and integer keys keep the oracle diff
    bit-exact on every engine including pallas/f32."""
    from repro.api import IndexConfig, LearnedIndex
    from repro.workloads import (PRESETS, WorkloadDivergence, WorkloadRunner,
                                 generate_stream)
    spec = PRESETS[preset].scaled(n_ops=N_WORKLOAD_OPS,
                                  batch_size=N_WORKLOAD_BATCH)
    keys = workload_universe()
    suffixes = {"off": "", "incremental": ",maint", "background": ",bg",
                "norecluster": ",maint,norecluster"}
    if maint_mode == "compare":
        runs = [("", "off"), (",maint", "incremental")]
    elif maint_mode == "recluster-compare":
        # the zipfian splice-locality ablation: incremental maintenance
        # with vs without heat-driven segment re-clustering — the merge
        # p50 / dirty-row-fraction delta re-clustering buys
        runs = [(",maint", "incremental"),
                (",maint,norecluster", "norecluster")]
    else:
        runs = [(suffixes[maint_mode], maint_mode)]
    sections: dict = {}
    for suffix, mode in runs:
        print(f"# workload: {preset} on the '{ENGINE}' engine "
              f"({spec.n_ops} ops, oracle-checked, maintenance={mode})")
        # default (auto) merge policy: write-heavy mixes must exercise the
        # overlay -> merge -> republish lifecycle, not pile into the overlay
        ix = LearnedIndex.build(keys, config=IndexConfig(
            engine=ENGINE, sample_stride=4, overlay_cap=8192,
            maintenance=_maint_config(mode),
            telemetry=bool(METRICS_JSON)))
        rep = WorkloadRunner(ix).run(generate_stream(spec, keys), spec=spec)
        d = rep.to_json_dict()
        d["maintenance"] = mode
        d["n_keys"] = len(keys)     # per-section scale stamp: sections at
        # different BENCH_N_KEYS coexist in one artifact self-describingly
        # flush = the synchronous barrier: folds the tail of pending
        # writes and drains any in-flight background merge, so the
        # reported counts/percentiles are deterministic and complete
        # (sampling mid-fold used to report merges=0 racily)
        st = ix.flush()
        if st.get("maint_errors"):
            # the runner's in-stream check can race an in-flight worker;
            # errors are cumulative, so re-assert after the flush barrier
            raise WorkloadDivergence(
                f"{preset}: {st['maint_errors']} background maintenance "
                f"task(s) failed\n" + "\n".join(st.get("maint_error_logs",
                                                       [])))
        d["n_merges"] = st["n_merges"]
        d["epoch"] = st["epoch"]
        d.update(_latency_percentiles(ix.maint_timings()))
        d["n_retrains"] = st["n_retrains"]
        d["n_incremental_flattens"] = st["n_incremental_flattens"]
        d["n_reclusters"] = st.get("n_reclusters", 0)
        d["n_forced_full_flattens"] = st.get("n_forced_full_flattens", 0)
        # retrace watchdog: the runner marked warm after its warmup
        # batches, so any later trace is a regression (the PR-4 bug class)
        m = ix.metrics()
        d["post_warmup_retraces"] = m["retrace"]["post_warmup_traces"]
        d["retraces_per_1k_ops"] = m["retrace"]["retraces_per_1k_ops"]
        ix.close()
        tag = f"workload,{preset}{suffix}"
        csv_row(f"{tag},{ENGINE},ops_per_s", d["ops_per_s"],
                f"n_ops={d['n_ops']};merges={d['n_merges']};"
                f"epoch={d['epoch']};divergences={d['n_divergences']};"
                f"maintenance={mode}")
        for op, n in rep.op_counts.items():
            if n:
                csv_row(f"{tag},{ENGINE},{op}_us",
                        1e6 * rep.op_seconds[op] / n, f"n={n}")
        if d.get("n_publishes"):
            csv_row(f"{tag},{ENGINE},merge_ms_p50", d["merge_ms_p50"],
                    f"p95={d['merge_ms_p95']:.1f};"
                    f"p99={d['merge_ms_p99']:.1f};max={d['merge_ms_max']:.1f}")
            csv_row(f"{tag},{ENGINE},publish_ms_p50", d["publish_ms_p50"],
                    f"p95={d['publish_ms_p95']:.1f};"
                    f"p99={d['publish_ms_p99']:.1f};"
                    f"max={d['publish_ms_max']:.1f};"
                    f"dirty={d['dirty_row_fraction_mean']:.3f}")
        sections[tag] = d
        if METRICS_JSON:
            METRICS_SECTIONS[tag] = _metrics_section(m, len(keys))
    return sections


N_RECOVERY_RECORDS = int(os.environ.get("BENCH_RECOVERY_RECORDS", "10000"))


def durability_bench() -> dict:
    """Durability sections for BENCH_PR2.json (``--durability``), same
    one-dict-per-section schema as every other extra section:

      durability,wal_overhead   ycsb_a throughput with durability off vs
                                fsync="interval" (overhead_frac: DESIGN.md
                                section 14 targets <= 0.15)
      durability,recovery       wall time to recover a checkpoint plus a
                                BENCH_RECOVERY_RECORDS-record WAL tail,
                                split into the recovery.load/replay spans
      durability,kill_recover   ycsb_a replayed halfway, index abandoned
                                (a SIGKILL's disk state), recovered, and
                                the rest of the stream continued on the
                                recovered index — oracle-checked, so any
                                divergence raises and fails the run
    """
    import shutil
    import tempfile
    import time as _t
    from repro.api import IndexConfig, LearnedIndex, manual_merge_policy
    from repro.durability import DurabilityConfig
    from repro.workloads import PRESETS, WorkloadRunner, generate_stream
    keys = workload_universe()
    spec = PRESETS["ycsb_a"].scaled(n_ops=N_WORKLOAD_OPS,
                                    batch_size=N_WORKLOAD_BATCH)
    root = tempfile.mkdtemp(prefix="dili_dur_bench_")
    sections: dict = {}
    try:
        # -- WAL-append overhead: the same stream, throughput-only runner,
        # durability off vs group-commit + interval fsync
        print(f"# durability: ycsb_a WAL overhead on the '{ENGINE}' engine "
              f"({spec.n_ops} ops, fsync=interval)")
        ops_per_s: dict = {}
        for label in ("warmup", "off", "interval"):
            # checkpoint_every_merges=8: the section isolates the per-write
            # WAL append + group-commit cost; the default every-merge
            # cadence folds full-snapshot checkpoint writes into the same
            # number (~3 merges in this stream => +40% at 300k keys),
            # which the recovery section already prices separately
            dur = None if label in ("warmup", "off") else DurabilityConfig(
                dir=os.path.join(root, "overhead"), fsync="interval",
                checkpoint_every_merges=8)
            ix = LearnedIndex.build(keys, config=IndexConfig(
                engine=ENGINE, sample_stride=4, overlay_cap=8192,
                durability=dur))
            rep = WorkloadRunner(ix, check=False).run(
                generate_stream(spec, keys), spec=spec,
                name=f"ycsb_a[durability={label}]")
            ix.flush()
            ix.close()
            # the warmup pass exists to mint every executable the stream
            # needs (process-wide jit cache) so neither timed leg pays
            # compile costs; its throughput is discarded
            ops_per_s[label] = rep.ops_per_s
        overhead = 1.0 - ops_per_s["interval"] / ops_per_s["off"]
        sections["durability,wal_overhead"] = dict(
            n_keys=len(keys),
            preset="ycsb_a", engine=ENGINE, fsync="interval",
            checkpoint_every_merges=8,
            n_ops=spec.n_ops, base_ops_per_s=ops_per_s["off"],
            durable_ops_per_s=ops_per_s["interval"],
            overhead_frac=overhead)
        csv_row(f"durability,wal_overhead,{ENGINE},ops_per_s",
                ops_per_s["interval"],
                f"base={ops_per_s['off']:.0f};"
                f"overhead_frac={overhead:.3f};fsync=interval")
        # -- recovery time: one checkpoint + an N_RECOVERY_RECORDS-record
        # tail (manual merges: no publish, so nothing truncates the WAL)
        print(f"# durability: recovery of a {N_RECOVERY_RECORDS}-record "
              f"WAL tail on the '{ENGINE}' engine")
        rdir = os.path.join(root, "recovery")
        ix = LearnedIndex.build(keys, config=IndexConfig(
            engine=ENGINE, sample_stride=4, overlay_cap=1 << 20,
            merge=manual_merge_policy(),
            durability=DurabilityConfig(dir=rdir, fsync="interval")))
        rng = np.random.default_rng(21)
        pool = keys[rng.integers(0, len(keys), 8192)]
        for i in range(N_RECOVERY_RECORDS):
            k = pool[(4 * i) % 8192: (4 * i) % 8192 + 4]
            ix.upsert(k, np.full(len(k), i, np.int64))
        ix.abandon()                 # no final fsync: a crash's disk state
        t0 = _t.perf_counter()
        rix = LearnedIndex.recover(rdir)
        recovery_s = _t.perf_counter() - t0
        m = rix.metrics()
        spans = m["spans"]
        sections["durability,recovery"] = dict(
            n_keys=len(keys),
            engine=ENGINE, tail_records=N_RECOVERY_RECORDS,
            recovery_s=recovery_s,
            replayed_records=int(m["counters"]
                                 ["recovery.replayed_records"]),
            load_ms=spans["recovery.load"]["ms_mean"],
            replay_ms=spans["recovery.replay"]["ms_mean"],
            publish_ms=spans["recovery.publish"]["ms_mean"])
        rix.close()
        csv_row(f"durability,recovery,{ENGINE},recovery_s", recovery_s,
                f"tail_records={N_RECOVERY_RECORDS};"
                f"load_ms={spans['recovery.load']['ms_mean']:.1f};"
                f"replay_ms={spans['recovery.replay']['ms_mean']:.1f}")
        # -- kill-and-recover replay: differential, strict — divergence
        # raises out of the benchmark run
        print(f"# durability: ycsb_a kill-and-recover replay on the "
              f"'{ENGINE}' engine (oracle-checked)")
        ix = LearnedIndex.build(keys, config=IndexConfig(
            engine=ENGINE, sample_stride=4, overlay_cap=8192,
            durability=DurabilityConfig(
                dir=os.path.join(root, "kill"), fsync="interval")))
        runner = WorkloadRunner(ix)
        batches = generate_stream(spec, keys)
        kr = runner.run_kill_recover(batches, kill_at=len(batches) // 2,
                                     spec=spec, name="ycsb_a")
        runner.index.close()
        sections["durability,kill_recover"] = dict(
            n_keys=len(keys),
            engine=ENGINE, preset="ycsb_a",
            kill_at_batch=kr["kill_at_batch"],
            recovery_s=kr["recovery_s"],
            replayed_records=kr["replayed_records"],
            n_divergences=kr["n_divergences"])
        csv_row(f"durability,kill_recover,{ENGINE},recovery_s",
                kr["recovery_s"],
                f"kill_at_batch={kr['kill_at_batch']};"
                f"replayed={kr['replayed_records']};"
                f"divergences={kr['n_divergences']}")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return sections


# --serve sizing knobs: ops per load leg (capped), concurrent client
# streams, ops per client request (small on purpose — coalescing many
# small requests into pow2 facade batches is the thing under test),
# initial offered rate for the saturation ramp, and target leg seconds
N_SERVE_OPS = int(os.environ.get("BENCH_SERVE_OPS",
                                 str(min(N_WORKLOAD_OPS, 20000))))
N_SERVE_CLIENTS = int(os.environ.get("BENCH_SERVE_CLIENTS", "4"))
N_SERVE_REQ_OPS = int(os.environ.get("BENCH_SERVE_REQ_OPS", "16"))
SERVE_START_RATE = float(os.environ.get("BENCH_SERVE_START_RATE", "4000"))
SERVE_LEG_S = float(os.environ.get("BENCH_SERVE_LEG_S", "2.0"))


class _StreamTap:
    """Sequential request supply for the serving legs: one generator
    stream consumed front to back (slices must stay in order — later
    deletes name keys earlier slices inserted), regenerated with a fresh
    seed when it runs dry.  Perf legs only; the oracle leg uses its own
    single dedicated stream."""

    def __init__(self, spec, keys):
        self.spec, self.keys = spec, keys
        self.seed = spec.seed
        self._refill()

    def _refill(self):
        from repro.workloads import generate_stream
        self.batches = generate_stream(
            self.spec.scaled(seed=self.seed), self.keys)
        self.seed += 1
        self.i = 0

    def take(self, n_ops: int) -> list:
        out, got = [], 0
        while got < n_ops:
            if self.i >= len(self.batches):
                self._refill()
            b = self.batches[self.i]
            self.i += 1
            out.append(b)
            got += b.n_ops
        return out


def _serve_leg_ops(rate: float) -> int:
    return int(np.clip(rate * SERVE_LEG_S, 1000, N_SERVE_OPS))


def serve_index(keys, *, background: bool, telemetry: bool):
    from repro.api import IndexConfig, LearnedIndex, MaintenanceConfig
    return LearnedIndex.build(keys, config=IndexConfig(
        engine=ENGINE, sample_stride=4, overlay_cap=8192,
        maintenance=MaintenanceConfig(background=background),
        telemetry=telemetry))


def warm_serve_buckets(ix, keys, cfg) -> None:
    """Mint every read-path executable the coalescer can reach (pow2
    lane buckets from one request up to the batch cap) before the timed
    legs, then anchor the retrace watchdog."""
    k0 = float(keys[0])
    b = 64
    while b <= cfg.max_batch_ops:
        ix.lookup(np.full(b, k0))
        ix.range(np.full(b, k0), np.full(b, k0 + 4.0),
                 max_hits=cfg.max_hits)
        b *= 2
    ix.telemetry.mark_warm()


def journal_replay_check(served, journal, keys, tag: str) -> dict:
    """The serving oracle leg: replay a served run's commit-order journal
    through `WorkloadRunner` on a fresh index over the same `keys`.  The
    replay diffs every batch against `SortedOracle` and raises on any
    divergence (strict, CI-visible); the fresh index's final items() must
    then equal the served index's bit-exactly, or this raises too."""
    from repro.workloads import WorkloadRunner
    fresh = serve_index(keys, background=False, telemetry=False)
    try:
        wrep = WorkloadRunner(fresh).run(journal, name=f"{tag},replay")
        k1, v1 = served.items()
        k2, v2 = fresh.items()
    finally:
        fresh.close()
    if not (np.array_equal(k1, k2) and np.array_equal(v1, v2)):
        raise AssertionError(
            f"{tag}: concurrent run's final items() diverged from its "
            f"own journal's deterministic replay")
    return dict(n_divergences=len(wrep.divergences),
                journal_batches=len(journal), checked_ops=wrep.n_ops,
                bit_exact=True)


def _leg_brief(rep) -> dict:
    lat = rep.latency_ms()
    return dict(offered_ops_per_s=rep.offered_ops_per_s,
                achieved_ops_per_s=rep.achieved_ops_per_s,
                n_ops=rep.n_ops, shed_frac=rep.shed_frac,
                late_submits=rep.late_submits,
                lookup_ms_p99=lat.get("lookup", {}).get("ms_p99"))


def serve_bench(preset: str) -> dict:
    """Concurrent serving sections for BENCH_PR2.json (``--serve``): the
    open-loop throughput-latency curve of the `repro.serve` front-end on
    ENGINE (DESIGN.md section 15).

    Per preset, one `serve,<preset>` section recording

      * saturation_ops_per_s — geometric offered-rate ramp until the
        batcher stops keeping up; best achieved rate across legs;
      * latency_at — full p50/p95/p99/p999 end-to-end (scheduled arrival
        -> completion) per op at 50%/80%/95% of saturation;
      * oracle — a journaled run at 50% saturation replayed through
        `WorkloadRunner` on a fresh index: any batch-level divergence
        raises, and the fresh index's final items() must equal the
        served index's bit-exactly (n_divergences is asserted 0 here,
        not just reported);
      * maintenance_compare — the SAME offered load against background
        vs synchronous maintenance (local engine; the ROADMAP's "prove
        background merges pay off under traffic" number).

    Request granularity is N_SERVE_REQ_OPS ops (default 16): small
    requests from N_SERVE_CLIENTS concurrent client threads, coalesced
    by the batcher into pow2 facade batches."""
    from repro.serve import ServeConfig, ServeFrontend, open_loop, \
        saturation_search
    from repro.workloads import PRESETS, generate_stream
    keys = workload_universe()
    spec = PRESETS[preset].scaled(n_ops=N_SERVE_OPS,
                                  batch_size=N_SERVE_REQ_OPS)
    scfg = ServeConfig()
    bg_main = ENGINE == "local"     # background maintenance is local-only
    tag = f"serve,{preset}"
    sec: dict = dict(engine=ENGINE, preset=preset, n_keys=len(keys),
                     n_clients=N_SERVE_CLIENTS, req_ops=N_SERVE_REQ_OPS,
                     background_maintenance=bg_main)

    # -- saturation ramp + latency legs on one served index ------------------
    print(f"# serve: {preset} on the '{ENGINE}' engine "
          f"({N_SERVE_CLIENTS} open-loop client streams, "
          f"{N_SERVE_REQ_OPS}-op requests)")
    ix = serve_index(keys, background=bg_main,
                      telemetry=bool(METRICS_JSON) or bool(TRACE_JSON))
    warm_serve_buckets(ix, keys, scfg)
    tap = _StreamTap(spec, keys)
    fe = ServeFrontend(ix, scfg, journal=False)

    def mk(_leg):
        # mirrors saturation_search's geometric ramp (factor=2.0 below)
        return tap.take(_serve_leg_ops(SERVE_START_RATE * (2.0 ** _leg)))

    sat, ramp = saturation_search(fe, mk, SERVE_START_RATE, factor=2.0,
                                  max_legs=7, n_clients=N_SERVE_CLIENTS)
    sec["saturation_ops_per_s"] = sat
    sec["ramp"] = [_leg_brief(l) for l in ramp]
    csv_row(f"{tag},{ENGINE},saturation_ops_per_s", sat,
            f"ramp_legs={len(ramp)};clients={N_SERVE_CLIENTS}")
    sec["latency_at"] = {}
    for li, frac in enumerate((0.5, 0.8, 0.95)):
        rate = frac * sat
        # --trace-json: arm causal tracing on the FIRST latency leg only
        # (the 50% one — comfortably under saturation, so the exported
        # queue/exec/facade/WAL/merge chains show steady-state serving,
        # not overload shedding)
        trace = TRACE_JSON if (TRACE_JSON and li == 0) else None
        rep = open_loop(fe, tap.take(_serve_leg_ops(rate)), rate,
                        n_clients=N_SERVE_CLIENTS, trace_path=trace)
        if trace:
            print(f"# serve: wrote causal trace {trace} "
                  f"(open in Perfetto / chrome://tracing)")
        d = rep.to_json_dict()
        sec["latency_at"][f"{int(frac * 100)}%"] = d
        lk = d["latency_ms"].get("lookup", {})
        csv_row(f"{tag},{ENGINE},p99_at_{int(frac * 100)}pct",
                lk.get("ms_p99", 0.0),
                f"rate={rate:.0f};achieved={rep.achieved_ops_per_s:.0f};"
                f"p50={lk.get('ms_p50', 0.0):.2f};"
                f"p999={lk.get('ms_p999', 0.0):.2f};"
                f"shed={rep.shed_frac:.3f}")
    sec["batcher"] = fe.stats()
    fe.close()
    if METRICS_JSON:
        METRICS_SECTIONS[tag] = _metrics_section(ix.metrics(), len(keys))
    ix.close()

    # -- oracle equivalence: journaled 50%-rate run, replayed ----------------
    print(f"# serve: {preset} oracle equivalence "
          f"(journal replay on a fresh index)")
    served = serve_index(keys, background=bg_main, telemetry=False)
    warm_serve_buckets(served, keys, scfg)
    fe = ServeFrontend(served, scfg, journal=True)
    ostream = generate_stream(spec.scaled(seed=spec.seed + 977), keys)
    orep = open_loop(fe, ostream, max(0.5 * sat, SERVE_START_RATE),
                     n_clients=N_SERVE_CLIENTS)
    journal = fe.journal_batches()
    fe.close()
    sec["oracle"] = dict(journal_replay_check(served, journal, keys, tag),
                         shed_frac=orep.shed_frac)
    served.close()
    csv_row(f"{tag},{ENGINE},oracle_divergences",
            sec["oracle"]["n_divergences"],
            f"journal_batches={len(journal)};bit_exact=True")

    # -- background vs sync maintenance under identical load -----------------
    sec["maintenance_compare"] = {}
    modes = (("background", True), ("sync", False)) if bg_main \
        else (("sync", False),)
    cmp_rate = 0.8 * sat
    for label, bg in modes:
        print(f"# serve: {preset} maintenance={label} at 80% saturation")
        cix = serve_index(keys, background=bg, telemetry=False)
        warm_serve_buckets(cix, keys, scfg)
        cfe = ServeFrontend(cix, scfg, journal=False)
        ctap = _StreamTap(spec.scaled(seed=spec.seed + 1531), keys)
        # 2x leg length: the comparison must cross the overlay-cap merge
        # threshold so maintenance actually runs inside the timed window
        crep = open_loop(cfe, ctap.take(2 * _serve_leg_ops(cmp_rate)),
                         cmp_rate, n_clients=N_SERVE_CLIENTS,
                         timeout_s=240.0)
        cfe.close()
        st = cix.stats()
        d = _leg_brief(crep)
        d["n_merges"] = st.get("n_merges")
        lat = crep.latency_ms().get("lookup", {})
        d["lookup_ms_p50"] = lat.get("ms_p50")
        d["lookup_ms_p999"] = lat.get("ms_p999")
        sec["maintenance_compare"][label] = d
        cix.close()
        csv_row(f"{tag},{ENGINE},maint_{label}_p99",
                d["lookup_ms_p99"] or 0.0,
                f"achieved={d['achieved_ops_per_s']:.0f};"
                f"merges={d['n_merges']};rate={cmp_rate:.0f}")
    if bg_main and "background" in sec["maintenance_compare"]:
        b = sec["maintenance_compare"]["background"]
        s = sec["maintenance_compare"]["sync"]
        if b["lookup_ms_p99"] and s["lookup_ms_p99"]:
            sec["maintenance_compare"]["p99_speedup_bg_over_sync"] = \
                s["lookup_ms_p99"] / b["lookup_ms_p99"]
    return {tag: sec}


def scale_bench() -> dict:
    """Scale sections for BENCH_PR2.json (``--scale``): build cost, peak
    memory footprint, and depth-resolved traversal cost at the CURRENT
    BENCH_N_KEYS, over the int64-valued workload universe (the same keys
    the oracle-checked workload legs use, so the numbers describe the
    serving configuration end to end).

      scale,build      bulk_load + flatten wall seconds, process peak RSS
                       (`peak_rss_mb` — the memory-footprint field the CI
                       scale leg asserts on), snapshot bytes/key, splice
                       segment count, and tree height stats
      scale,traversal  lookup ns/query at the REAL tree height of this
                       scale, decomposed per level (nodes walked) and per
                       memory touch (nodes + slot probes) — how lookup
                       cost actually grows with cardinality, not a
                       fixed-depth extrapolation
    """
    import resource
    import time as _t
    keys = workload_universe()
    print(f"# scale: build + traversal at n_keys={len(keys)}")
    t0 = _t.perf_counter()
    d = bulk_load(keys, sample_stride=4)
    build_s = _t.perf_counter() - t0
    t0 = _t.perf_counter()
    f = flatten(d)
    flatten_s = _t.perf_counter() - t0
    idx = DeviceSnapshot.from_flat(f)
    # ru_maxrss is KiB on Linux: the high-water mark across build+flatten
    # (host tree + snapshot both live), the number a capacity plan needs
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    s = d.stats()
    sections: dict = {}
    sections["scale,build"] = dict(
        n_keys=len(keys), build_s=build_s, flatten_s=flatten_s,
        peak_rss_mb=peak_rss_mb, flat_mb=f.nbytes() / 2 ** 20,
        bytes_per_key=f.nbytes() / len(keys), n_segments=f.n_segments,
        max_depth=f.max_depth, avg_height=s["avg_height"],
        conflicts_per_1k=1000.0 * s["conflicts"] / len(keys))
    csv_row(f"scale,build,n={len(keys)}", build_s,
            f"flatten_s={flatten_s:.2f};peak_rss_mb={peak_rss_mb:.0f};"
            f"bytes_per_key={f.nbytes() / len(keys):.1f};"
            f"segments={f.n_segments};max_depth={f.max_depth}")
    rng = np.random.default_rng(31)
    q = jnp.asarray(keys[rng.integers(0, len(keys), N_QUERIES)])
    t = time_fn(lambda q: S.search_batch(idx, q, early_exit=True), q)
    v, fnd, nodes, probes = S.search_batch(idx, q, with_stats=True)
    assert bool(np.asarray(fnd).all())
    mean_nodes = float(np.asarray(nodes).mean())
    mean_probes = float(np.asarray(probes).mean())
    ns = t / N_QUERIES * 1e9
    sections["scale,traversal"] = dict(
        n_keys=len(keys), ns_per_query=ns, max_depth=f.max_depth,
        mean_nodes=mean_nodes, mean_probes=mean_probes,
        ns_per_level=ns / max(mean_nodes, 1.0),
        ns_per_touch=ns / max(mean_nodes + mean_probes, 1.0))
    csv_row(f"scale,traversal,n={len(keys)}", ns,
            f"max_depth={f.max_depth};nodes={mean_nodes:.2f};"
            f"probes={mean_probes:.2f};"
            f"ns_per_level={ns / max(mean_nodes, 1.0):.1f}")
    return sections


ALL = [table4_lookup, table5_access, table6_stats, fig6_memory_range,
       fig7_workloads, fig8_deletions, table78_hyperparams, table9_breakdown,
       table10_12_13_appendix, fig9_scale, fig10_shift, online_mixed,
       kernel_bench, facade_bench]


def bench_pr2(out_path: str, extra_sections: dict | None = None) -> dict:
    """Hot-path trajectory artifact (BENCH_PR2.json): re-measure the PR-2
    hot paths ALONGSIDE the pre-PR numbers (benchmarks/baseline_pre_pr2.json,
    captured on the pre-PR tree at the same scales) with derived speedups.
    Since the api redesign the same file also records the facade numbers for
    the engine selected by --engine (same schema, new `engine` field +
    `facade_*` sections) — one format, extended, per ROADMAP."""
    import json
    from common import N_KEYS
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline_pre_pr2.json")
    baseline = {}
    if os.path.exists(base_path):
        baseline = json.load(open(base_path))
    if baseline and baseline.get("n_keys") != N_KEYS:
        # speedups are only meaningful at the baseline's scale
        print(f"# WARNING: baseline captured at n_keys={baseline.get('n_keys')}"
              f" but this run uses {N_KEYS}; skipping speedup comparison")
        baseline = {}
    base_sec = baseline.get("sections", {})
    print("# PR2: hot-path trajectory vs pre-PR baseline")
    out: dict = dict(n_keys=N_KEYS, n_queries=N_QUERIES,
                     baseline_n_keys=baseline.get("n_keys"),
                     engine=ENGINE,
                     cost_model="depth-exact traversal + early exit; "
                                "O(log n + max_hits) sorted-pair ranges",
                     sections={})
    for name in DATASETS:
        keys, d, f, idx = dili_for(name)
        q = jnp.asarray(queries_for(name))
        t = time_fn(lambda q: S.search_batch(idx, q, early_exit=True), q)
        new_ns = t / N_QUERIES * 1e9
        old = base_sec.get(f"point_lookup,{name}", {})
        old_ns = old.get("ns_per_query")
        out["sections"][f"point_lookup,{name}"] = dict(
            n_keys=N_KEYS,
            ns_per_query=new_ns, pre_pr_ns_per_query=old_ns,
            speedup=(old_ns / new_ns) if old_ns else None,
            max_depth=f.max_depth)
        csv_row(f"pr2,point_lookup,{name}", new_ns,
                f"pre_pr={old_ns};speedup="
                f"{(old_ns / new_ns) if old_ns else float('nan'):.2f}x")
        rng = np.random.default_rng(3)
        starts = rng.integers(0, len(keys) - 101, 512)
        lo = jnp.asarray(keys[starts])
        hi = jnp.asarray(keys[starts + 100])
        tr = time_fn(lambda lo, hi: S.range_query_batch(idx, lo, hi,
                                                        max_hits=128), lo, hi)
        new_us = tr / 512 * 1e6
        oldr = base_sec.get(f"range_query,{name}", {})
        old_us = oldr.get("us_per_query")
        out["sections"][f"range_query,{name}"] = dict(
            n_keys=N_KEYS,
            us_per_query=new_us, pre_pr_us_per_query=old_us,
            speedup=(old_us / new_us) if old_us else None,
            n_pairs=f.n_pairs)
        csv_row(f"pr2,range_query,{name}", new_us,
                f"pre_pr={old_us};speedup="
                f"{(old_us / new_us) if old_us else float('nan'):.2f}x")
        # facade serving path on the selected engine (host<->device
        # included) — same recipe as `--only facade` (_facade_measure)
        lookup_ns, range_us = _facade_measure(name)
        out["sections"][f"facade_lookup,{name}"] = dict(
            n_keys=N_KEYS, ns_per_query=lookup_ns, engine=ENGINE)
        out["sections"][f"facade_range,{name}"] = dict(
            n_keys=N_KEYS, us_per_query=range_us, engine=ENGINE)
        csv_row(f"pr2,facade_lookup,{name}", lookup_ns, f"engine={ENGINE}")
        csv_row(f"pr2,facade_range,{name}", range_us, f"engine={ENGINE}")
    if extra_sections:
        # mixed-workload sections from --workload: same artifact, same
        # one-dict-per-section schema (ROADMAP: extend, don't fork)
        out["sections"].update(extra_sections)
    with open(out_path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"# wrote {out_path}")
    return out


def bench_pr2_extend(out_path: str, extra_sections: dict) -> dict:
    """Merge this run's sections into an EXISTING BENCH_PR2.json without
    re-measuring (or perturbing a single byte of) what is already there —
    how different-scale runs accumulate in one trajectory artifact.

    Every section this run emits carries its own `n_keys` stamp; when the
    run's scale differs from the artifact's top-level `n_keys`, the new
    section keys additionally get an `@n=<scale>` suffix so a 10M
    `workload,ycsb_a,maint` lands NEXT TO the 300k section of the same
    name instead of overwriting it."""
    import json
    from common import N_KEYS
    with open(out_path) as fh:
        out = json.load(fh)
    suffix = "" if out.get("n_keys") == N_KEYS else f"@n={N_KEYS}"
    for tag, sec in extra_sections.items():
        out["sections"][tag + suffix] = sec
    with open(out_path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"# extended {out_path} with {len(extra_sections)} section(s)"
          f"{' at n_keys=' + str(N_KEYS) if suffix else ''}")
    return out


def main() -> None:
    import argparse
    import json
    from common import ROWS
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="write every CSV row (name/value/derived) here")
    ap.add_argument("--pr2-json", default="",
                    help="write the BENCH_PR2.json hot-path trajectory here "
                         "(skips the per-table sections unless --only set)")
    ap.add_argument("--engine", default="local",
                    choices=("local", "pallas", "sharded"),
                    help="LearnedIndex engine for the facade sections, "
                         "--workload, and --pr2-json")
    ap.add_argument("--workload", default="",
                    help="comma-separated workload presets (ycsb_a/b/c/e, "
                         "dili_paper, shift_fb_logn, ttl_storm) replayed "
                         "through the --engine facade with oracle "
                         "checking; one workload,<preset> section each; "
                         "BENCH_WORKLOAD_OPS sizes them")
    ap.add_argument("--serve", default="",
                    help="comma-separated workload presets driven through "
                         "the concurrent serving front-end (repro.serve) "
                         "under open-loop load on --engine: saturation "
                         "ramp, p50/p99/p999 at 50/80/95%% of saturation, "
                         "journal-replay oracle equivalence, and a "
                         "background-vs-sync maintenance comparison; one "
                         "serve,<preset> section each (BENCH_SERVE_OPS / "
                         "BENCH_SERVE_CLIENTS / BENCH_SERVE_REQ_OPS size "
                         "them)")
    ap.add_argument("--scale", action="store_true",
                    help="measure build time, peak RSS memory footprint, "
                         "and depth-resolved traversal cost at the current "
                         "BENCH_N_KEYS (scale,build + scale,traversal "
                         "sections)")
    ap.add_argument("--pr2-extend", default="",
                    help="merge this run's sections into an EXISTING "
                         "BENCH_PR2.json instead of regenerating it; "
                         "pre-existing sections stay byte-identical, and "
                         "sections measured at a different BENCH_N_KEYS "
                         "than the artifact get an @n=<scale> key suffix")
    ap.add_argument("--durability", action="store_true",
                    help="measure the durability subsystem on --engine: "
                         "ycsb_a WAL-append overhead (off vs "
                         "fsync=interval), recovery time for a "
                         "BENCH_RECOVERY_RECORDS-record WAL tail, and an "
                         "oracle-checked kill-and-recover replay; three "
                         "durability,* sections in BENCH_PR2.json")
    ap.add_argument("--trace-json", default="",
                    help="arm end-to-end causal tracing on the first "
                         "--serve latency leg and write the Chrome-trace-"
                         "event JSON here; open it in Perfetto to see each "
                         "request's queue_wait -> exec -> facade -> WAL "
                         "chain with linked merge spans")
    ap.add_argument("--metrics-json", default="",
                    help="build --workload indexes with telemetry enabled "
                         "and write their LearnedIndex.metrics() snapshots "
                         "(per-op histograms, merge-pipeline spans, retrace "
                         "watchdog) here, keyed by workload section")
    ap.add_argument("--maintenance", default="off",
                    choices=("off", "incremental", "background", "compare",
                             "norecluster", "recluster-compare"),
                    help="merge pipeline for --workload runs: legacy full "
                         "flatten (default — keeps pre-PR5 invocations at "
                         "their original cost), adaptive (splice+retrain), "
                         "background thread, 'compare' = off AND "
                         "incremental back-to-back (records the latency "
                         "delta; what BENCH_PR2.json is emitted with), "
                         "'norecluster' = adaptive with segment "
                         "re-clustering disabled, or 'recluster-compare' "
                         "= adaptive with AND without re-clustering "
                         "back-to-back (the zipfian splice-locality "
                         "ablation)")
    args = ap.parse_args()
    from repro.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    global ENGINE, METRICS_JSON, TRACE_JSON
    ENGINE = args.engine
    METRICS_JSON = args.metrics_json
    TRACE_JSON = args.trace_json
    if args.only or not (args.pr2_json or args.pr2_extend or args.workload
                         or args.durability or args.serve or args.scale):
        for fn in ALL:
            if args.only and args.only not in fn.__name__:
                continue
            fn()
    wl_sections: dict = {}
    if args.scale:
        wl_sections.update(scale_bench())
    if args.workload:
        for preset in args.workload.split(","):
            wl_sections.update(workload_bench(preset.strip(),
                                              args.maintenance))
    if args.serve:
        for preset in args.serve.split(","):
            wl_sections.update(serve_bench(preset.strip()))
    if args.durability:
        wl_sections.update(durability_bench())
    if args.pr2_json:
        bench_pr2(args.pr2_json, extra_sections=wl_sections)
    elif args.pr2_extend:
        bench_pr2_extend(args.pr2_extend, wl_sections)
    if args.metrics_json:
        with open(args.metrics_json, "w") as fh:
            json.dump(dict(engine=ENGINE, schema="dili.metrics/1",
                           sections=METRICS_SECTIONS), fh, indent=1)
        print(f"# wrote {args.metrics_json}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(dict(n_queries=N_QUERIES, rows=ROWS), fh, indent=1)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
