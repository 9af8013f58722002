"""The yardstick's arithmetic: percentiles over all requests, the idle
share as a union of intervals, the bytes a lookup must read, the peak
table, and the plain reference itself."""

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

from bench import harness, roofline, trace_reduce             # noqa: E402
from bench.loadgen import Window                              # noqa: E402
from bench.reference import SortedReference                   # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


class _Handle:
    def __init__(self, lat):
        self.done, self.error, self.latency_s, self.t_done = True, None, \
            lat, 0.0


class _Sent:
    def __init__(self, lat):
        self.handle = _Handle(lat)


def test_latency_percentiles_are_exact_over_all_requests():
    lat = np.random.default_rng(0).exponential(0.002, 10_001)
    win = Window("open", 1.0, sent=[_Sent(x) for x in lat])
    run = harness.Run(cell="c", config={}, mix={}, seconds=1.0, trace=False,
                      n_keys=1, setup_s=0.0, window=win, device_kind="x")
    srt = np.sort(lat)
    # 10,001 samples: the 50th and 99th percentiles are order statistics
    assert harness.load_reader(harness.BENCH_DIR, "p50_ms")(run) == \
        pytest.approx(srt[5000] * 1e3, rel=1e-12)
    assert harness.load_reader(harness.BENCH_DIR, "p99_ms")(run) == \
        pytest.approx(srt[9900] * 1e3, rel=1e-12)


def _extract(ops, window=(0.0, 100.0), plane="/device:TPU:0"):
    return {"devices": {plane: {"ops": [["op", s, d] for s, d in ops],
                                "modules": []}},
            "host": [[trace_reduce.WINDOW, window[0],
                      window[1] - window[0]]]}


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    # overlapping, nested and touching intervals, one past the window
    ex = _extract([(10, 10), (15, 10), (16, 2), (25, 5), (60, 5),
                   (95, 20)])
    # busy: [10, 30) + [60, 65) + [95, 100) = 30 of 100
    assert trace_reduce.idle_share(ex) == pytest.approx(70.0)
    assert trace_reduce.mean_busy_s(ex)[0] == pytest.approx(30e-9)
    gaps = trace_reduce.idle_gaps(ex)
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 30e-9, 10e-9])


def _raster_idle(ex, step_ns: float) -> float:
    lo, hi = trace_reduce.window_ns(ex)
    t = np.arange(lo, hi, step_ns) + step_ns / 2
    plane = sorted(ex["devices"])[0]
    busy = np.zeros(len(t), bool)
    for _, s, d in ex["devices"][plane]["ops"]:
        busy |= (t >= s) & (t < s + d)
    return 100.0 * (1.0 - busy.mean())


def test_idle_share_on_a_recorded_chip_trace():
    """A short extract of a traced run on a TPU v5 lite (the Pallas cell),
    reduced again and checked against a brute-force rasterization."""
    with gzip.open(os.path.join(DATA, "tpu_trace_extract.json.gz")) as f:
        ex = json.load(f)
    lo, hi = trace_reduce.window_ns(ex)
    step = (hi - lo) / 200_000
    assert trace_reduce.idle_share(ex) == pytest.approx(
        _raster_idle(ex, step), abs=0.05)
    assert 0.0 < trace_reduce.idle_share(ex) < 100.0


def test_bytes_per_query_is_the_same_for_kernel_and_xla_tables():
    """One snapshot, searched by the Pallas kernel or by the XLA
    traversal: the reckoning reads the same tree and gives one count."""
    from repro.api import DeviceSnapshot
    from repro.core.flat import flatten
    from repro.kernels import ops as K
    keys = np.sort(np.random.default_rng(1).permutation(40_000)[:20_000]
                   ).astype(np.float64)
    d, _ = K.build_f32_index(keys, sample_stride=4)
    flat = flatten(d)
    kern = roofline.bytes_per_query(
        {k: np.asarray(v) for k, v in K.kernel_arrays(flat).items()
         if k != "max_depth"})
    snap = DeviceSnapshot.from_flat(flat, dtype=np.float32, pad=True)
    xla = roofline.bytes_per_query({k: np.asarray(v)
                                    for k, v in snap.arrays.items()})
    assert kern == xla
    hist = roofline.depth_histogram({k: np.asarray(v) for k, v in
                                     snap.arrays.items()})
    assert sum(hist.values()) == len(keys)
    # at least one level: a model (2 x 4 B) and a child id, plus a slot
    assert kern >= (2 * 4 + 4) + (4 + 8)


def test_unknown_device_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_reference_matches_a_dict_model():
    rng = np.random.default_rng(5)
    keys = np.unique(rng.uniform(0, 1e6, 500))
    ref = SortedReference(keys, np.arange(len(keys)))
    model = dict(zip(keys.tolist(), range(len(keys))))
    for step in range(20):
        k = np.concatenate([rng.choice(keys, 5), rng.uniform(0, 1e6, 5)])
        v = rng.integers(0, 1 << 40, len(k))
        if step % 3 == 2:
            ref.delete(k)
            for x in k.tolist():
                model.pop(x, None)
        else:
            ref.upsert(k, v)
            model.update(zip(k.tolist(), v.tolist()))
        q = np.concatenate([k, rng.uniform(0, 1e6, 5)])
        got_v, got_f = ref.lookup(q)
        for x, gv, gf in zip(q.tolist(), got_v, got_f):
            assert gf == (x in model) and (not gf or gv == model[x])
    lo = np.array([0.0, 2e5]), np.array([1e6, 2e5 + 1e4])
    ks, vs, cnt = ref.range(*lo, max_hits=64)
    srt = sorted(model)
    for i, (a, b) in enumerate(zip(*lo)):
        inside = [x for x in srt if a <= x < b]
        assert cnt[i] == min(len(inside), 64)
        assert ks[i][:cnt[i]].tolist() == inside[:64]
        assert vs[i][:cnt[i]].tolist() == [model[x] for x in inside[:64]]
        assert np.isinf(ks[i][cnt[i]:]).all() and (vs[i][cnt[i]:] == -1).all()
    k_all, v_all = ref.items()
    assert k_all.tolist() == srt and v_all.tolist() == [model[x] for x in srt]


def test_key_sets_are_ycsbs():
    """YCSB's hashed insert order: record 0 is the well-known key
    `user6284781860667377211`; the ordered one is the record numbers."""
    from bench.keys import fnvhash64, make_keys
    assert fnvhash64(np.arange(2)).tolist() == [6284781860667377211,
                                                8517097267634966620]
    k = make_keys("hashed", 5000)
    assert len(k) == 5000 and (np.diff(k) > 0).all() and k.min() >= 0
    assert 6284781860667377211.0 in k
    assert make_keys("ordered", 7).tolist() == list(range(7))
    with pytest.raises(ValueError):
        make_keys("fb", 10)


def _run(**kw):
    return harness.Run(cell="c", config={}, mix={}, seconds=1.0,
                       trace=True, n_keys=1000, setup_s=0.0,
                       window=Window("open", 1.0), device_kind="x", **kw)


@pytest.mark.parametrize("split", ["local", "tput"])
def test_one_reader_serves_a_quantity_split_by_cell(split):
    """`engine.exec_ms.local` and `.tput` have no file of their own: both
    are read by `engine.exec_ms.py`."""
    read = harness.load_reader(harness.BENCH_DIR, f"engine.exec_ms.{split}")
    run = _run(spans={"serve.exec": {"count": 3, "ms_mean": 2.5}})
    assert read(run) == 2.5
    with pytest.raises(FileNotFoundError):
        harness.load_reader(harness.BENCH_DIR, "no_such.metric")


def test_bytes_per_key_reads_memory_after_the_drain_not_the_peak():
    read = harness.load_reader(harness.BENCH_DIR, "bytes_per_key")
    assert read(_run(peak_bytes=9_000_000, live_bytes=250_000)) == 250.0
    assert read(_run(peak_bytes=9_000_000)) is None


def test_merge_ms_counts_only_merges_published_in_the_window():
    read = harness.load_reader(harness.BENCH_DIR, "maint.merge_ms")

    def spans(n, fold, publish):
        return {"merge.fold": {"count": n, "ms_mean": fold},
                "merge.retrain": {"count": 0, "ms_mean": 0.0},
                "merge.publish": {"count": n, "ms_mean": publish}}
    # two set-up merges of 1,000 + 10 ms, then two in the window of 40 + 2
    before = spans(2, 1000.0, 10.0)
    after = spans(4, (2 * 1000.0 + 2 * 40.0) / 4, (2 * 10.0 + 2 * 2.0) / 4)
    assert read(_run(spans_before=before, spans=after)) == \
        pytest.approx(42.0)
    assert read(_run(spans_before=before, spans=before)) is None
