"""The readers of the program's own spans and counters, and the idle-gap
naming by span self time; with the existing readers pinned on the
recorded chip extract, so that adding these moved none of them."""

import gzip
import json
import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

import numpy as np                                            # noqa: E402
import pytest                                                 # noqa: E402

from bench import harness, host_spans, trace_reduce           # noqa: E402
from bench.loadgen import Window                              # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("engine.fetch_ms", "engine.host_ms", "engine.syncs_per_batch",
       "frontend.worker_idle_share")


def _load(name):
    with gzip.open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _read(metric, run):
    return harness.load_reader(harness.BENCH_DIR, metric)(run)


class _Req:
    op, n_ops = "lookup", 4096


class _Handle:
    done, error = True, None


class _Sent:
    req, handle = _Req(), _Handle()


def _run(**kw):
    kw.setdefault("window", Window("open", 10.0))
    return harness.Run(cell="c", config={}, mix={}, seconds=10.0,
                       trace=True, n_keys=1000, setup_s=0.0,
                       device_kind="TPU v5 lite", **kw)


def _split(named):
    """([name, ...], [seconds, ...]) of a breakdown list."""
    return [n for n, _ in named], [t for _, t in named]


def _summary(count, ms_mean):
    return {"count": count, "ms_mean": ms_mean}


def test_existing_readers_unchanged_on_the_recorded_extract():
    """Every reader of the device trace, and the breakdown, give on the
    checked-in extract (a multiget window on a TPU v5 lite) what they gave
    before the program's spans were added."""
    ex = _load("tpu_trace_extract.json.gz")
    run = _run(trace_extract=ex, bytes_per_query=36.0,
               window=Window("closed", 10.0, sent=[_Sent()]))
    assert _read("device.idle_share.tput", run) == \
        pytest.approx(62.28065, rel=1e-12)
    assert _read("lookup.device_ns_per_query.tput", run) == \
        pytest.approx(14085858.0 / 4096, rel=1e-12)
    assert _read("lookup.device_ns_per_query.local", run) is None
    assert _read("dili_search_roofline", run) == pytest.approx(
        100.0 * 36.0 * 4096 / 819e9 / 14085858e-9, rel=1e-12)
    assert _split(trace_reduce.top_ops(ex, 2)) == (
        ["dili_search.1 tpu_custom_call", "fusion.15"],
        pytest.approx([0.014085858, 0.001532925], rel=1e-12))
    assert _split(trace_reduce.idle_gaps(ex, 2)) == (
        ["bench.client.submit"] * 2,
        pytest.approx([0.004860782, 0.00477595], rel=1e-12))


def test_span_readers_are_window_deltas_over_batches():
    before = {"serve.exec": _summary(10, 5.0),
              "engine.fetch": _summary(30, 1.0)}
    after = {"serve.exec": _summary(110, 6.0),      # 100 batches, 610 ms
             "engine.fetch": _summary(330, 1.5)}    # 300 reads, 465 ms
    run = _run(spans_before=before, spans=after,
               serve_before={"n_batches": 10}, serve_after={"n_batches": 110})
    assert _read("engine.fetch_ms.tput", run) == pytest.approx(4.65)
    assert _read("engine.host_ms.local", run) == pytest.approx(6.1 - 4.65)
    assert _read("engine.syncs_per_batch.local", run) == 3.0


def test_worker_idle_share_cuts_the_waits_at_the_window():
    """The wait in progress when the window opened counts from the
    window's start; the one in progress after the drain up to the drain."""
    win = Window("open", 10.0, t0=100.0, t_drained=110.0)
    run = _run(window=win,
               serve_before={"worker_idle_s": 50.0,
                             "worker_idle_since": 95.0},
               serve_after={"worker_idle_s": 50.0 + 5.0 + 3.0,
                            "worker_idle_since": 109.0})
    # 5 s of that first wait lay before the window: 3 + 1 inside it
    assert _read("frontend.worker_idle_share", run) == pytest.approx(40.0)
    idle_all = _run(window=win,
                    serve_before={"worker_idle_s": 0.0,
                                  "worker_idle_since": 90.0},
                    serve_after={"worker_idle_s": 0.0,
                                 "worker_idle_since": 90.0})
    assert _read("frontend.worker_idle_share", idle_all) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("metric", [m + s for m in NEW[:3]
                                    for s in (".local", ".tput")]
                         + [NEW[3]])
def test_span_readers_find_nothing_on_a_program_without_the_spans(metric):
    """A program without the spans (the parent of this change) leaves the
    metrics out instead of failing the run."""
    run = _run(spans={"serve.exec": _summary(5, 1.0)},
               spans_before={"serve.exec": _summary(1, 1.0)},
               serve_before={"n_batches": 1, "completed_ops": 1},
               serve_after={"n_batches": 5, "completed_ops": 5})
    assert _read(metric, run) is None


def _cells():
    from test_bench_harness import CELLS
    return [c for c, _, _ in CELLS]


@pytest.mark.parametrize("cell", _cells())
def test_traced_cpu_run_reports_the_new_metrics(tmp_path, cell):
    """A traced run at a tiny size reports every new metric its cell
    lists, with the program's counts: one result read per local batch,
    route, recheck and result per Pallas batch."""
    from test_bench_harness import run, tiny_bench
    bench, bench_dir = tiny_bench(tmp_path)
    res = run(bench, bench_dir, cell, trace=True)
    assert res["correct"], res["checks"]
    want = {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", ()) and m["name"].startswith(NEW)}
    assert want and want <= set(res["metrics"])
    got = {k: v["value"] for k, v in res["metrics"].items()}
    syncs = next(v for k, v in got.items() if k.startswith(NEW[2]))
    if cell.startswith("pallas"):
        assert syncs == 3.0
    else:
        # write batches read nothing back
        assert 0.0 < syncs <= 1.0
        assert 0.0 < got["frontend.worker_idle_share"] <= 100.0
    assert all(v >= 0.0 for k, v in got.items() if k.startswith(NEW))


def _ex(ops, spans, window=(0.0, 300.0)):
    return {"devices": {"/device:TPU:0": {
                "ops": [["op", s, d] for s, d in ops], "modules": []}},
            "host": [[trace_reduce.WINDOW, window[0],
                      window[1] - window[0]],
                     ["bench.client.submit", 150.0, 150.0]],
            "spans": spans}


def test_self_time_is_the_innermost_span_of_each_thread():
    spans = [["serve.exec", 10, 80, "w"], ["engine.prep", 12, 5, "w"],
             ["engine.fetch", 30, 50, "w"], ["merge.fold", 5, 100, "m"]]
    assert host_spans.self_time(spans) == [
        ("serve.exec", 10, 12, "w"), ("engine.prep", 12, 17, "w"),
        ("serve.exec", 17, 30, "w"), ("engine.fetch", 30, 80, "w"),
        ("serve.exec", 80, 90, "w"), ("merge.fold", 5, 105, "m")]


def test_gaps_named_by_self_time_gc_first_merges_beside():
    spans = [["serve.wait_for_work", 0, 10, "w"], ["serve.exec", 10, 80, "w"],
             ["engine.fetch", 30, 50, "w"], ["merge.fold", 5, 100, "m"],
             ["serve.wait_for_work", 90, 120, "w"],
             ["host.gc", 200, 90, "c"]]
    # idle: [20, 60), [160, 300)
    ex = _ex([(0, 20), (60, 100)], spans)
    assert _split(host_spans.idle_gaps(ex)) == (
        ["host.gc", "engine.fetch+merge.fold"],
        pytest.approx([140e-9, 40e-9]))
    # without spans the benchmark's own annotation names a gap, as before
    bare = dict(ex, spans=[])
    assert [g[0] for g in host_spans.idle_gaps(bare)] == \
        ["bench.client.submit", "unannotated"]
    w = host_spans.worker_threads(ex)
    assert w == {"w"}
    # under the worker: [20, 60) and [160, 210); under gc: [200, 290)
    assert host_spans.idle_share_under(
        ex, names=("host.gc",), threads=w) == pytest.approx(100 * 170 / 180)
    assert host_spans.idle_share_under(ex, names=("host.gc",)) == \
        pytest.approx(100 * 90 / 180)


def _raster(ex, n=200_000):
    """Sample points of the window, which of them the first device left
    idle, and for each thread the span innermost at each point."""
    lo, hi = trace_reduce.window_ns(ex)
    t = lo + (np.arange(n) + 0.5) * (hi - lo) / n
    busy = np.zeros(n, bool)
    for _, s, d in ex["devices"][sorted(ex["devices"])[0]]["ops"]:
        busy |= (t >= s) & (t < s + d)
    inner = {}
    for name, s, d, th in sorted(ex["spans"], key=lambda e: (e[1], -e[2])):
        col = inner.setdefault(th, np.full(n, None, object))
        col[(t >= s) & (t < s + d)] = name      # later starts nest inside
    return t, ~busy, inner


@pytest.mark.parametrize("cell,longest", [
    ("local_hashed.ycsb_c", "host.gc"),
    ("pallas_ordered.multiget", "engine.fetch")])
def test_gap_names_and_cover_on_a_recorded_chip_trace(cell, longest):
    """Cuts of two traced runs on a TPU v5 lite with the program's spans
    (a ycsb_c collection and a multiget stretch), checked against a
    brute-force rasterization: the longest gap's name by the innermost
    span over most of it, and the idle share under the worker's spans or
    a collection."""
    ex = _load("tpu_trace_spans.json.gz")[cell]
    t, idle, inner = _raster(ex)
    gaps = host_spans.idle_gaps(ex, 3)
    assert gaps[0][0] == longest
    g0, g1 = host_spans.idle_intervals(ex)[np.argmax(np.diff(
        host_spans.idle_intervals(ex), axis=1))]
    inside = (t >= g0) & (t < g1)
    votes = {}
    for col in inner.values():
        for name in col[inside]:
            if name is not None:
                votes[name] = votes.get(name, 0) + 1
    assert max(votes, key=votes.get) == longest
    workers = host_spans.worker_threads(ex)
    under = np.zeros(len(t), bool)
    for name, s, d, th in ex["spans"]:
        if th in workers or name == "host.gc":
            under |= (t >= s) & (t < s + d)
    raster = 100.0 * (under & idle).sum() / idle.sum()
    assert host_spans.idle_share_under(
        ex, names=("host.gc",), threads=workers) == \
        pytest.approx(raster, abs=0.1)
