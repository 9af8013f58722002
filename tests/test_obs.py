"""Telemetry core tests (DESIGN.md section 13): histogram accuracy vs the
shared percentile recipe, snapshot schema equivalence across engines,
merge-pipeline span taxonomy, the retrace watchdog (zero post-warmup
traces on a mixed sharded workload — the PR-4 regression class), and the
enabled-telemetry overhead budget."""

import json
import time

import numpy as np
import pytest

from repro.api import IndexConfig, LearnedIndex, MaintenanceConfig
from repro.obs import (ENGINE_SPANS, MERGE_SPANS, NULL_TELEMETRY, OPS,
                       RECOVERY_SPANS, LatencyHistogram, MetricsRegistry,
                       Telemetry, latency_summary, watchdog)

ENGINES = ("local", "pallas", "sharded")


def _universe(n=4096, seed=0):
    # integer keys: exactly representable in f32 so the pallas engine can
    # participate in cross-engine comparisons
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, 10 * n, n)).astype(np.float64)
    return keys, np.arange(len(keys), dtype=np.int64)


# -- metrics primitives -------------------------------------------------------


def test_histogram_matches_latency_summary():
    """The bucketed estimate must agree with the exact recipe to within
    the bucket layout's relative error (<= 1/32 per sample, upper edge)."""
    rng = np.random.default_rng(1)
    xs = rng.lognormal(mean=-7.0, sigma=1.5, size=20_000)   # ~1ms-ish
    h = LatencyHistogram()
    for x in xs:
        h.record(float(x))
    exact = latency_summary(xs)
    est = h.summary()
    assert est["count"] == exact["count"] == len(xs)
    for key in ("ms_p50", "ms_p95", "ms_p99", "ms_p999", "ms_max"):
        assert est[key] == pytest.approx(exact[key], rel=0.05), key
    assert est["ms_mean"] == pytest.approx(exact["ms_mean"], rel=1e-9)


def test_histogram_extremes_and_empty():
    h = LatencyHistogram()
    empty = h.summary("op")
    assert empty["op_count"] == 0 and empty["op_ms_p999"] == 0.0
    h.record(0.0)                      # below T_MIN: first bucket
    h.record(1e9)                      # beyond the table: overflow bucket
    s = h.summary()
    assert s["count"] == 2
    assert s["ms_max"] == pytest.approx(1e12)          # exact max kept
    assert h.quantile(1.0) == pytest.approx(1e9)


def test_latency_summary_stable_schema():
    """Empty and non-empty summaries expose the same key set — engines
    with quiet ops must still export an identical schema."""
    assert set(latency_summary([])) == set(latency_summary([1e-3, 2e-3]))


def test_registry_snapshot_jsonable():
    reg = MetricsRegistry()
    reg.count("merges")
    reg.count("merges", 2)
    reg.gauge("fill", 0.5)
    reg.declare_histogram("op.lookup")
    reg.observe("op.lookup", 1e-3)
    reg.observe("op.other", 2e-3)          # lazy creation
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["merges"] == 3
    assert snap["gauges"]["fill"] == 0.5
    assert snap["histograms"]["op.lookup"]["count"] == 1
    assert snap["histograms"]["op.other"]["count"] == 1


def test_null_telemetry_costs_nothing_visible():
    t = NULL_TELEMETRY
    before = t.ops_total
    t.count_ops(5)
    with t.span("merge.fold"):
        pass
    t.record_span("merge.publish", 1e-3)
    assert t.ops_total == before + 5
    assert t.spans.count("merge.fold") == 0        # disabled: not recorded
    assert t.spans.count("merge.publish") == 0
    t.ops_total = before                            # shared instance: restore


def test_telemetry_snapshot_fixed_taxonomy():
    t = Telemetry(enabled=True)
    snap = t.snapshot()
    assert snap["schema"] == "dili.metrics/1"
    assert set(snap["ops"]) == set(OPS)
    assert set(snap["spans"]) == set(MERGE_SPANS + RECOVERY_SPANS
                                     + ENGINE_SPANS)
    # recovery.* spans are pre-declared: zero-filled summaries with the
    # full latency_summary key set BEFORE any recovery has ever run, so
    # a fresh index and a recovered one export the same schema
    for s in RECOVERY_SPANS:
        assert s.startswith("recovery."), s
        assert snap["spans"][s]["count"] == 0, s
        assert set(snap["spans"][s]) == set(latency_summary([])) | {"count"}
    assert snap["retrace"]["post_warmup_traces"] == 0
    json.dumps(snap)


def test_registry_warn_rate_limited():
    """Structured warnings: the Python warning fires once per registry
    (rate limit), while the `warn.<name>` counter keeps accumulating the
    full magnitude — and declaring the counter never emits anything."""
    reg = MetricsRegistry()
    with pytest.warns(UserWarning, match="7 keys collided"):
        reg.warn("collisions", "7 keys collided", count=7)
    # subsequent calls are silent but still counted
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        reg.warn("collisions", "3 more", count=3)
        reg.warn("collisions", "5 more", count=5)
    assert reg.snapshot()["counters"]["warn.collisions"] == 15
    # rate-limit bookkeeping must NOT leak into the counter schema
    assert set(reg.snapshot()["counters"]) == {"warn.collisions"}


# -- watchdog -----------------------------------------------------------------


def test_watchdog_counts_fresh_traces():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return x * 2 + 1

    watchdog.register_jit("test.probe", probe)
    mark = watchdog.TraceMark.now()
    probe(jnp.arange(7))                   # first call: traces
    assert watchdog.TraceMark.now().delta() == dict(traces=0, compiles=0)
    d = mark.delta()
    assert d["traces"] >= 1
    assert watchdog.jit_cache_sizes()["test.probe"] == 1
    mark2 = watchdog.TraceMark.now()
    probe(jnp.arange(7))                   # cached: no new trace
    assert mark2.delta()["traces"] == 0
    probe(jnp.arange(9))                   # new shape: re-trace
    assert mark2.delta()["traces"] >= 1
    assert watchdog.jit_cache_sizes()["test.probe"] == 2


# -- facade integration -------------------------------------------------------


def _exercise(ix, keys):
    q = keys[:128]
    v, f = ix.lookup(q)
    assert bool(f.all())
    ix.upsert(keys[:16] + 0.0, np.arange(16))
    ix.delete(keys[4:6])
    ix.range(keys[0], keys[64], max_hits=16)
    ix.flush()
    ix.lookup(q)


@pytest.mark.parametrize("engine", ENGINES)
def test_metrics_off_by_default_but_counting(engine):
    keys, vals = _universe()
    ix = LearnedIndex.build(keys, vals, config=IndexConfig(engine=engine))
    _exercise(ix, keys)
    m = ix.metrics()
    assert not m["enabled"]
    assert m["ops_total"] > 0                          # counting stays live
    assert all(m["ops"][op]["count"] == 0 for op in OPS)   # no capture
    assert all(m["spans"][s]["count"] == 0 for s in MERGE_SPANS)
    ix.close()


def test_metrics_schema_equivalent_across_engines():
    """Pinned acceptance criterion: metrics() returns the SAME key tree on
    every engine (jit_cache_entries excepted — its members are process-
    global registrations, identical here but not schema-guaranteed)."""
    keys, vals = _universe()

    def shape(d, prefix=""):
        out = []
        for k in sorted(d):
            out.append(prefix + k)
            if isinstance(d[k], dict):
                out += shape(d[k], prefix + k + ".")
        return [k for k in out
                if not k.startswith("retrace.jit_cache_entries.")]

    shapes = {}
    for engine in ENGINES:
        ix = LearnedIndex.build(keys, vals, config=IndexConfig(
            engine=engine, telemetry=True))
        _exercise(ix, keys)
        ix.telemetry.mark_warm()
        m = ix.metrics()
        json.dumps(m)
        assert m["enabled"] and m["engine"] == engine
        assert m["ops"]["lookup"]["count"] > 0
        # the declared-everywhere surfaces ride along on every engine:
        # recovery.* spans (zero-filled without a recovery) and the
        # structured-warning counter (zero unless the pallas quantizer
        # actually collided)
        assert set(RECOVERY_SPANS) <= set(m["spans"])
        assert "warn.pallas_f32_collision" in m["counters"]
        shapes[engine] = shape(m)
        ix.close()
    assert shapes["local"] == shapes["pallas"] == shapes["sharded"]


@pytest.mark.parametrize("engine,per_lookup,whats", [
    ("local", 1, {"result"}),
    ("pallas", 3, {"route", "recheck", "result"})])
def test_host_syncs_per_lookup(engine, per_lookup, whats):
    """Each blocking device-to-host read is one `engine.fetch` span and
    one `engine.host_syncs` count: the local engine reads its results
    once; the Pallas engine also reads the kernel's route flag and the
    recheck's patched-lane count.  Pending writes add no read."""
    keys, vals = _universe()
    ix = LearnedIndex.build(keys, vals, config=IndexConfig(
        engine=engine, telemetry=True))
    tel = ix.telemetry
    ix.lookup(keys[:64])
    for pending in (False, True):
        if pending:
            ix.upsert(keys[:4] + 1.0, np.arange(4))
        syncs = tel.metrics.counters["engine.host_syncs"]
        fetches = tel.spans.count("engine.fetch")
        for _ in range(5):
            ix.lookup(keys[:64])
        assert tel.metrics.counters["engine.host_syncs"] - syncs == \
            5 * per_lookup
        assert tel.spans.count("engine.fetch") - fetches == 5 * per_lookup
    got = {s.attrs["what"] for s in tel.spans.spans("engine.fetch")}
    assert got == whats
    ix.close()


def test_disabled_telemetry_records_and_annotates_nothing(monkeypatch):
    """Telemetry off: no span, no sync count, and no profiler annotation
    is even built on the served path, collections included."""
    import gc
    from repro.obs import telemetry as T, tracing
    from repro.serve import ServeFrontend
    built = []

    def spy(name, **attrs):
        built.append(name)

    monkeypatch.setattr(tracing, "trace_annotation", spy)
    monkeypatch.setattr(T, "trace_annotation", spy)
    keys, vals = _universe()
    for on in (False, True):
        ix = LearnedIndex.build(keys, vals, config=IndexConfig(
            engine="local", telemetry=on))
        fe = ServeFrontend(ix)
        c = fe.client("c")
        c.lookup(keys[:3])
        c.upsert(keys[:2] + 1.0, [1, 2])
        gc.collect()
        fe.close()
        m = ix.metrics()
        ix.close()
        if not on:
            assert built == []
            assert all(s["count"] == 0 for s in m["spans"].values())
            assert m["counters"]["engine.host_syncs"] == 0
    assert {"serve.exec", "engine.fetch", "host.gc"} <= set(built)


def test_stats_shared_across_engines():
    """The EngineTelemetryBase mixin keeps the stats() core uniform."""
    keys, vals = _universe()
    for engine in ENGINES:
        ix = LearnedIndex.build(keys, vals, config=IndexConfig(engine=engine))
        s = ix.stats()
        for key in ("engine", "epoch", "n_flattens", "n_merges",
                    "telemetry_enabled", "ops_total", "maint_errors"):
            assert key in s, (engine, key)
        assert s["engine"] == engine
        ix.close()


def test_merge_pipeline_spans_background():
    """The full span taxonomy must fire across a background merge —
    including queue_wait (submit->worker start) and frozen_dwell
    (freeze->drop), which only exist on the scheduler path."""
    keys, vals = _universe()
    ix = LearnedIndex.build(keys, vals, config=IndexConfig(
        engine="local", telemetry=True,
        maintenance=MaintenanceConfig(background=True)))
    rng = np.random.default_rng(2)
    for _ in range(6):
        ks = rng.integers(1, 10 * len(keys), 512).astype(np.float64)
        ix.upsert(ks, np.arange(512))
    ix.flush()
    m = ix.metrics()
    counts = {s: m["spans"][s]["count"] for s in MERGE_SPANS}
    for s in ("merge.fold", "merge.flatten", "merge.publish",
              "merge.queue_wait", "merge.frozen_dwell"):
        assert counts[s] > 0, (s, counts)
    # retrain spans require the retrain pipeline; default config has it on
    assert m["spans"]["merge.fold"]["ms_p50"] > 0.0
    assert m["counters"]["publish.retraced"] >= 0
    ix.close()


def test_workload_runner_latency_and_warmup():
    from repro.workloads import PRESETS, WorkloadRunner, generate_stream
    keys, vals = _universe()
    ix = LearnedIndex.build(keys, vals, config=IndexConfig(
        engine="local", telemetry=True))
    spec = PRESETS["ycsb_a"].scaled(n_ops=2000, batch_size=128)
    rep = WorkloadRunner(ix, warmup_batches=4).run(
        generate_stream(spec, keys), spec=spec)
    from repro.workloads.generator import OPS as WORKLOAD_OPS
    d = rep.to_json_dict()
    assert set(d["latency_ms"]) == set(WORKLOAD_OPS)
    assert d["latency_ms"]["lookup"]["count"] > 0
    assert d["latency_ms"]["lookup"]["ms_p999"] >= \
        d["latency_ms"]["lookup"]["ms_p50"] > 0
    json.dumps(d)
    assert ix.telemetry.warmed                     # runner marked warm
    ix.close()


# -- the regression the subsystem exists for ---------------------------------


def test_zero_post_warmup_retraces_sharded_mixed():
    """PR-4 bug class: the sharded collectives once re-traced EVERY batch
    (~50x per-batch cost) with results staying correct.  After the
    runner's warmup (which pre-mints every pow2 batch bucket the stream
    can reach), a steady mixed workload must mint NO new executables."""
    from repro.workloads import PRESETS, WorkloadRunner, generate_stream
    keys, vals = _universe()
    ix = LearnedIndex.build(keys, vals, config=IndexConfig(
        engine="sharded", telemetry=True))
    spec = PRESETS["ycsb_a"].scaled(n_ops=3000, batch_size=128)
    WorkloadRunner(ix, warmup_batches=4).run(
        generate_stream(spec, keys), spec=spec)
    r = ix.metrics()["retrace"]
    assert r["warmed"]
    assert r["post_warmup_ops"] > 0
    assert r["post_warmup_traces"] == 0, r
    assert r["retraces_per_1k_ops"] == 0.0
    ix.close()


@pytest.mark.parametrize("vmem_budget", [12 * 1024 * 1024, 1024])
def test_zero_post_warmup_retraces_pallas_mixed(vmem_budget):
    """Same contract on the pallas engine, on BOTH sides of the
    kernel-dispatch boundary: with the default VMEM budget the snapshot
    tables fit and lookups go through the Pallas kernel wrapper; with a
    tiny budget every batch dispatches to the XLA fallback.  Either way
    a steady mixed workload after warmup must mint no new executables —
    and crossing the boundary must be a BUILD-time decision, never a
    per-batch retrace."""
    from repro.workloads import PRESETS, WorkloadRunner, generate_stream
    keys, vals = _universe()
    ix = LearnedIndex.build(keys, vals, config=IndexConfig(
        engine="pallas", telemetry=True, vmem_budget_bytes=vmem_budget))
    spec = PRESETS["ycsb_a"].scaled(n_ops=3000, batch_size=128)
    WorkloadRunner(ix, warmup_batches=4).run(
        generate_stream(spec, keys), spec=spec)
    r = ix.metrics()["retrace"]
    assert r["warmed"]
    assert r["post_warmup_ops"] > 0
    assert r["post_warmup_traces"] == 0, (vmem_budget, r)
    assert r["retraces_per_1k_ops"] == 0.0
    ix.close()


@pytest.mark.slow
def test_enabled_telemetry_overhead_budget():
    """config.telemetry=True must cost <= 3% on the ycsb_c-style point-
    lookup loop (plus a small absolute slack for timer noise at this
    scale).  Interleaved median-of-batches keeps the comparison fair."""
    keys, vals = _universe(n=20_000, seed=3)
    q = keys[:1024]
    pair = [LearnedIndex.build(keys, vals, config=IndexConfig(
        engine="local", telemetry=t)) for t in (False, True)]
    for ix in pair:
        for _ in range(5):
            ix.lookup(q)                       # warm both executables
    times: list[list[float]] = [[], []]
    for _ in range(60):
        for which, ix in enumerate(pair):
            t0 = time.perf_counter()
            ix.lookup(q)
            times[which].append(time.perf_counter() - t0)
    off, on = (float(np.median(t)) for t in times)
    assert on <= off * 1.03 + 5e-5, (off, on)
    for ix in pair:
        ix.close()
