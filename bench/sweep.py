"""Find a cell's knee once, on the chip: the highest offered rate the
served index keeps up with.

    python bench/sweep.py --workload local_hashed.ycsb_c --seed 1 \
        --start 2000 --factor 1.5 --legs 10 --leg-seconds 4

Builds and warms the cell's index as a run does, then offers the mix open
loop at a geometric ramp of rates, one leg each, and prints per leg the
offered and achieved rates, the shed and late shares and the latency
tails.  It stops after the first leg that does not keep up
(`loadgen.kept_up`: achieved under 90% of offered, or more than 1% shed).
Each row also counts the merges published so far: a leg in which a
merge ran measured the merge, not the steady write path.
A cell's mix file then takes about 4/5 of the last rate kept up.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_ENABLE_X64"] = "1"
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--factor", type=float, default=1.5)
    ap.add_argument("--legs", type=int, default=10)
    ap.add_argument("--leg-seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, loadgen
    from bench.keys import make_keys
    from bench.traffic import Traffic
    from repro.api import LearnedIndex
    from repro.compile_cache import enable_persistent_cache
    from repro.serve import RejectedError, ServeConfig, ServeFrontend

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    enable_persistent_cache()
    bench = harness.load_benchmark(ROOT)
    spec = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = harness.load_data(harness.BENCH_DIR, "configs", spec["config"])
    mix = harness.load_data(harness.BENCH_DIR, "traffic", spec["traffic"])
    keys = make_keys(cfg["key_shape"], cfg["n_keys"])
    vals = np.arange(len(keys), dtype=np.int64)
    t0 = time.perf_counter()
    ix = LearnedIndex.build(keys, vals, harness.index_config(cfg, False))
    serve_cfg = ServeConfig(**mix.get("serve", {}))
    harness.warm(ix, keys, vals, Traffic(mix, keys, args.seed), serve_cfg)
    print(f"sweep: {args.workload} n_keys={len(keys)} "
          f"setup_s={time.perf_counter() - t0:.1f}", flush=True)
    fe = ServeFrontend(ix, serve_cfg, journal=False)
    for leg, rate in enumerate(loadgen.sweep_rates(args.start, args.factor,
                                                   args.legs)):
        traffic = Traffic(dict(mix, rate_ops_per_s=rate), keys,
                          args.seed + leg)
        win = loadgen.open_loop(fe, traffic.open_loop(args.leg_seconds),
                                rate, args.leg_seconds, int(mix["clients"]),
                                rejected=RejectedError)
        done_ops = sum(s.req.n_ops for s in win.done())
        offered_ops = done_ops + win.shed_ops
        achieved = done_ops / (win.t_drained - win.t0)
        shed = win.shed_ops / max(offered_ops, 1)
        lat = np.asarray(win.latencies_s()) * 1e3
        row = dict(offered=rate, achieved=round(achieved, 1),
                   shed_share=shed, late_share=win.late / win.n_scheduled,
                   p50_ms=float(np.percentile(lat, 50)),
                   p99_ms=float(np.percentile(lat, 99)),
                   batches=fe.stats()["n_batches"],
                   merges=ix.stats()["n_merges"])
        print("sweep: " + json.dumps(row), flush=True)
        if not loadgen.kept_up(rate, achieved, shed):
            break
    fe.close()
    ix.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
