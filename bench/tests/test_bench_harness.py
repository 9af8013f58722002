"""The harness on the CPU at a tiny size: files found by name, a whole run
through the test hook, and the check that decides `correct`.

These tests load no TPU library: the hook skips the look for a chip."""

import copy
import json
import os
import shutil
import sys
import time

os.environ.setdefault("JAX_ENABLE_X64", "1")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

import numpy as np                                            # noqa: E402
import pytest                                                 # noqa: E402

from bench import harness                                     # noqa: E402

TINY = {"local_f64_hashed": 3000, "pallas_f32_ordered": 3000}


#: every configuration and mix under bench/, as a cell, whether or not
#: BENCHMARK.json runs it on the chip
CELLS = [("local_hashed.ycsb_c", "local_f64_hashed", "ycsb_c"),
         ("local_hashed.ycsb_a", "local_f64_hashed", "ycsb_a"),
         ("pallas_ordered.multiget", "pallas_f32_ordered", "multiget")]


def tiny_bench(tmp_path, rate=2000.0):
    """A copy of the benchmark whose configurations hold a few thousand
    keys and whose open-loop mixes run at `rate`."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, n in TINY.items():
        p = bench_dir / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg["n_keys"] = n
        if cfg["maintenance"] == "background":
            cfg["merge"] = {"max_writes": 256}   # merges inside the window
        p.write_text(json.dumps(cfg))
    for p in (bench_dir / "traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        if mix["loop"] == "open":
            mix["rate_ops_per_s"] = rate
        p.write_text(json.dumps(mix))
    bench = harness.load_benchmark(ROOT)
    known = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for n, c, t in CELLS if n not in known]
    for m in bench["end_to_end"]:
        if m["name"] == "p50_ms":        # the test-only cells report p50
            m["workloads"] += [n for n, _, _ in CELLS if n not in known]
    return bench, str(bench_dir)


def run(bench, bench_dir, cell, seed=3, seconds=0.5, trace=False, **kw):
    return harness.run_cell(bench, cell, seed, seconds, trace,
                            t_start=time.perf_counter(),
                            bench_dir=bench_dir, require_tpu=False,
                            log=lambda m: None, **kw)


@pytest.mark.parametrize("cell", ["local_hashed.ycsb_c", "local_hashed.ycsb_a",
                                  "pallas_ordered.multiget"])
def test_cell_runs_correct_on_cpu(tmp_path, cell):
    bench, bench_dir = tiny_bench(tmp_path)
    res = run(bench, bench_dir, cell)
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
    # bytes_per_key needs the device's memory stats, which the CPU lacks
    assert set(res["metrics"]) == names - {"bytes_per_key"}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert list(res)[-1] == "checks"


def test_config_mix_and_metric_added_as_files_are_found(tmp_path):
    """A later change adds a cell by adding files only: a configuration,
    a traffic mix (here with scans and inserts) and a metric reader."""
    bench, bench_dir = tiny_bench(tmp_path)
    d = tmp_path / "bench"
    cfg = json.loads((d / "configs" / "local_f64_hashed.json").read_text())
    cfg.update(name="local_f64_ordered", key_shape="ordered", n_keys=2000)
    (d / "configs" / "local_f64_ordered.json").write_text(json.dumps(cfg))
    (d / "traffic" / "scan_insert.json").write_text(json.dumps({
        "name": "scan_insert", "ops": {"scan": 0.5, "insert": 0.3,
                                       "read": 0.2},
        "keys_per_request": 2, "popularity": {"dist": "latest"},
        "scan_len": [1, 100], "loop": "open", "rate_ops_per_s": 1000,
        "clients": 2, "serve": {"max_hits": 128}}))
    (d / "metrics" / "scans_served.py").write_text(
        "def read(run):\n"
        "    return sum(1 for s in run.window.done()"
        " if s.req.op == 'range')\n")
    bench = copy.deepcopy(bench)
    bench["workloads"].append({"name": "local_ordered.scan_insert",
                               "config": "local_f64_ordered",
                               "traffic": "scan_insert", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "scans_served", "unit": "reqs",
                               "better": "higher", "source": "host_clock",
                               "layer": "client", "moves": "p50_ms",
                               "workloads": ["local_ordered.scan_insert"]})
    res = run(bench, bench_dir, "local_ordered.scan_insert", trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["scans_served"]["value"] > 0


class _Faulty:
    """The index under test with a fault planted under the timed path."""

    def __init__(self, ix, fault):
        self._ix, self._fault = ix, fault

    def __getattr__(self, name):
        return getattr(self._ix, name)

    def lookup(self, q):
        v, f = self._ix.lookup(q)
        v, f = v.copy(), f.copy()
        if self._fault == "altered_answer" and f.any():
            v[np.argmax(f)] += 1              # one answer, where produced
        if self._fault == "half_batch":
            f[len(f) // 2:] = False           # the second half left out
        return v, f

    def upsert(self, keys, vals):
        if self._fault != "unchanged_state":  # a write that changes nothing
            self._ix.upsert(keys, vals)


@pytest.mark.parametrize("fault,cell", [
    ("altered_answer", "local_hashed.ycsb_c"),
    ("unchanged_state", "local_hashed.ycsb_a"),
    ("half_batch", "pallas_ordered.multiget")])
def test_planted_fault_makes_correct_false(tmp_path, fault, cell):
    from repro.api import LearnedIndex
    bench, bench_dir = tiny_bench(tmp_path)
    res = run(bench, bench_dir, cell, make_index=lambda k, v, c: _Faulty(
        LearnedIndex.build(k, v, c), fault))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["local_hashed.ycsb_c", "local_hashed.ycsb_a",
                                  "pallas_ordered.multiget"])
def test_precision_control_is_not_correct(tmp_path, cell):
    """The configuration's control (its key dtype narrowed one step) must
    fail the check that the program passes."""
    from bench import control
    bench, bench_dir = tiny_bench(tmp_path)
    res = control.run_control(bench, cell, 3, 0.5, bench_dir=bench_dir,
                              require_tpu=False, log=lambda m: None)
    assert not res["correct"], res["checks"]


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    """On a machine with no TPU the command prints no result."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "local_hashed.ycsb_c", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120, cwd=tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
