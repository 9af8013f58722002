"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name `BENCHMARK.json` gives it:

  bench/configs/<config>.json    engine, key shape and count, key dtype,
                                 maintenance, guarantees
  bench/traffic/<mix>.json       parameters for `traffic.Traffic`
  bench/metrics/<metric>.py      a reader: `read(run) -> float | None`;
                                 a metric `a.b.c` with no file of its own
                                 is read by `a.b.py`, so one reader serves
                                 the names that split one quantity by the
                                 end-to-end metric it moves

A run builds the index through the program's own bulk load, warms the
executables the cell's traffic reaches, drives the window through
`ServeClient.submit` on a `ServeFrontend`, drains, reads the device's
memory, and only then replays the journal through the plain reference
(`reference.check`).  The metrics are read from the `Run`
below; a reader that finds nothing returns None and its metric is left
out of the result line.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DATA_EXT = (".json",)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


# -- finding things by name ----------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _one_file(bench_dir: str, kind: str, name: str, exts) -> str:
    hits = [p for p in glob.glob(os.path.join(bench_dir, kind, name + ".*"))
            if os.path.splitext(p)[1] in exts
            and os.path.basename(p)[:-len(os.path.splitext(p)[1])] == name]
    if len(hits) != 1:
        raise FileNotFoundError(f"{kind}/{name}: expected one file with "
                                f"an extension in {exts}, found {hits}")
    return hits[0]


def load_data(bench_dir: str, kind: str, name: str) -> dict:
    with open(_one_file(bench_dir, kind, name, DATA_EXT)) as f:
        return json.load(f)


def load_reader(bench_dir: str, metric: str):
    """The `read(run)` function of `bench/metrics/<metric>.py`, or, where
    that file is missing, of the reader named by `metric` less its last
    dotted part (`engine.exec_ms.local` -> `engine.exec_ms.py`)."""
    name = metric
    while not os.path.exists(os.path.join(bench_dir, "metrics",
                                          name + ".py")) and "." in name:
        name = name.rsplit(".", 1)[0]
    path = _one_file(bench_dir, "metrics", name, (".py",))
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics, or
    with a trace its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# -- what readers read ---------------------------------------------------------


@dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: str
    config: dict
    mix: dict
    seconds: float
    trace: bool
    n_keys: int
    setup_s: float
    window: object                    # loadgen.Window
    device_kind: str
    peak_bytes: int | None = None     # fullest chip, process lifetime
    live_bytes: int | None = None     # fullest chip, after the drain
    serve_before: dict = field(default_factory=dict)   # batcher stats()
    serve_after: dict = field(default_factory=dict)
    spans: dict = field(default_factory=dict)   # index metrics()["spans"]
    spans_before: dict = field(default_factory=dict)  # at window start
    trace_extract: dict | None = None           # trace_reduce.extract
    bytes_per_query: float | None = None        # roofline.bytes_per_query
    post_warm_compiles: int = 0
    gc_pauses: list = field(default_factory=list)  # [(start, end, gen)]

    def latencies_s(self) -> np.ndarray:
        return np.asarray(self.window.latencies_s(), np.float64)

    def lookup_ops(self) -> int:
        return sum(s.req.n_ops for s in self.window.done()
                   if s.req.op == "lookup")


# -- the index under test ------------------------------------------------------


def index_config(cfg: dict, telemetry: bool, key_dtype=None):
    from repro.api import IndexConfig, MaintenanceConfig
    from repro.online import MergePolicy
    maint = cfg.get("maintenance", "off")
    return IndexConfig(
        engine=cfg["engine"],
        merge=MergePolicy(**cfg.get("merge", {})),
        dtype=np.dtype(key_dtype or cfg["key_dtype"]),
        sample_stride=int(cfg["sample_stride"]),
        overlay_cap=int(cfg["overlay_cap"]),
        maintenance=(None if maint == "off" else MaintenanceConfig(
            background=(maint == "background"))),
        telemetry=telemetry)


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def warm(ix, keys: np.ndarray, vals: np.ndarray, traffic, serve_cfg) -> None:
    """Mint every executable the cell's traffic reaches: the lookup (and,
    for scans, range) executables of each pow2 batch bucket from one
    request up to the coalescing cap, with and without pending writes
    when the mix writes, and one merge.  Writes here put back the values
    the keys already hold, so the index's content is unchanged."""
    lo = _pow2(max(traffic.kpr, 1))
    hi = max(_pow2(serve_cfg.max_batch_ops), lo)
    buckets = []
    b = lo
    while b <= hi:
        buckets.append(b)
        b *= 2

    def reads():
        for b in buckets:
            q = keys[np.arange(b) % len(keys)]
            ix.lookup(q)
            if traffic.scans:
                ix.range(q, q + 1.0, max_hits=serve_cfg.max_hits)

    reads()
    if traffic.writes:
        ix.upsert(keys[:1], vals[:1])
        reads()                            # reads over pending writes
        ix.flush()                         # one merge and publish
        reads()


# -- one run -------------------------------------------------------------------


def _device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def _device_bytes(jax, stat: str) -> int | None:
    """`memory_stats()[stat]` of the fullest chip."""
    got = [(d.memory_stats() or {}).get(stat) for d in jax.devices()]
    got = [b for b in got if b is not None]
    return int(max(got)) if got else None


def _gc_recorder():
    """A `gc.callbacks` hook recording each collection's (start, end,
    generation) on the host clock, and the list it fills."""
    pauses, start = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append((start[0], time.perf_counter(),
                           info["generation"]))
    return pauses, on_gc


def _wait_maintenance(ix, timeout_s: float = 120.0) -> None:
    deadline = time.perf_counter() + timeout_s
    while (ix.stats().get("maint_queue_depth", 0)
           and time.perf_counter() < deadline):
        time.sleep(0.05)


def run_cell(bench: dict, cell: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, bench_dir: str = BENCH_DIR,
             require_tpu: bool = True, make_index=None,
             key_dtype=None, log=None) -> dict:
    """One run; returns the result dict (the last stdout line).

    `make_index(keys, vals, index_config)` replaces the program's build
    (tests and the precision control use it); `key_dtype` overrides the
    configuration's key dtype for the index under test only."""
    import jax

    from . import loadgen, reference, roofline, trace_reduce
    from .keys import make_keys
    from .traffic import Traffic

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = {w["name"]: w for w in bench["workloads"]}[cell]
    device = _device_info(jax, int(spec["chips"]), require_tpu)
    from repro.api import LearnedIndex
    from repro.obs import watchdog
    from repro.serve import RejectedError, ServeConfig, ServeFrontend

    cfg = load_data(bench_dir, "configs", spec["config"])
    mix = load_data(bench_dir, "traffic", spec["traffic"])
    keys = make_keys(cfg["key_shape"], cfg["n_keys"])
    vals = np.arange(len(keys), dtype=np.int64)
    icfg = index_config(cfg, telemetry=trace, key_dtype=key_dtype)
    build = make_index or (lambda k, v, c: LearnedIndex.build(k, v, c))
    ix = build(keys, vals, icfg)
    traffic = Traffic(mix, keys, seed)
    serve_cfg = ServeConfig(**mix.get("serve", {}))
    reqs = (traffic.open_loop(seconds) if mix["loop"] == "open" else None)
    warm(ix, keys, vals, traffic, serve_cfg)
    _wait_maintenance(ix)
    tel = getattr(ix, "telemetry", None)
    if tel is not None:
        tel.mark_warm()
    mark = watchdog.TraceMark.now()
    snap = getattr(ix, "snapshot", None)
    bpq = None
    if trace and snap is not None:
        bpq = roofline.bytes_per_query(
            {k: np.asarray(v) for k, v in snap.arrays.items()})
    fe = ServeFrontend(ix, serve_cfg, journal=True)
    # the bulk load leaves millions of young objects behind; collect them
    # now, so that the window does not pay for set-up's garbage and every
    # run starts from the same collector state
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"bench: {cell} seed={seed} n_keys={len(keys)} setup_s={setup_s:.3f}")

    serve_before = fe.stats()
    spans_before = ix.metrics()["spans"] if tel is not None else {}
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # device ops and the benchmark's own annotations; no Python tracer,
        # which would slow every host call in the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    note = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))
    clients = int(mix["clients"])
    gc_pauses, on_gc = _gc_recorder()
    if trace:
        gc.callbacks.append(on_gc)
    with note(trace_reduce.WINDOW):
        if reqs is not None:
            win = loadgen.open_loop(fe, reqs, float(mix["rate_ops_per_s"]),
                                    seconds, clients, rejected=RejectedError,
                                    annotate=note)
        else:
            win = loadgen.closed_loop(fe, traffic, seconds, clients,
                                      rejected=RejectedError, annotate=note)
    extract = None
    if trace:
        gc.callbacks.remove(on_gc)
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                       recursive=True)
        extract = trace_reduce.extract(pb[0]) if pb else None
        shutil.rmtree(tmp, ignore_errors=True)
    _wait_maintenance(ix)
    peak = _device_bytes(jax, "peak_bytes_in_use")
    live = _device_bytes(jax, "bytes_in_use")
    post = mark.delta()
    stats = ix.stats()
    log("bench: index " + json.dumps(
        {k: stats[k] for k in ("n_merges", "kernel_routes") if k in stats},
        default=str))
    serve_after = fe.stats()
    journal = fe.journal_batches()
    spans = ix.metrics()["spans"] if tel is not None else {}
    final = ix.items()
    fe.close()
    ix.close()
    del ix, fe

    verdict = reference.check(win, journal, (keys, vals), final,
                              max_hits=serve_cfg.max_hits,
                              key_dtype=np.dtype(cfg["key_dtype"]))
    log(f"bench: post-warm-up compiles={post['compiles']} "
        f"traces={post['traces']}")
    run = Run(cell=cell, config=cfg, mix=mix, seconds=seconds, trace=trace,
              n_keys=len(keys), setup_s=setup_s, window=win,
              device_kind=device["kind"], peak_bytes=peak,
              live_bytes=live,
              serve_before=serve_before, serve_after=serve_after,
              spans=spans, spans_before=spans_before, trace_extract=extract,
              bytes_per_query=bpq, post_warm_compiles=post["compiles"],
              gc_pauses=gc_pauses)
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        value = load_reader(bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": verdict.correct,
              "attempted": len(win.sent) + win.shed_reqs,
              "failed": win.shed_reqs + verdict.unanswered,
              "metrics": metrics, "device": device}
    if trace and extract is not None:
        busy, window_s = trace_reduce.mean_busy_s(extract)
        device.update(busy_s=busy, window_s=window_s)
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(extract),
                               "idle_gaps": trace_reduce.idle_gaps(extract)}
    log(f"bench: answers_checked={verdict.answers_checked} "
        f"requests={len(win.sent)} shed={win.shed_reqs} late={win.late}")
    result["checks"] = verdict.checks()
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
