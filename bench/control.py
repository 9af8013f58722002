"""The precision control: a run of a cell whose index computes in the
nearest precision below the one its configuration states.  Its `correct`
has to come out false; its compared numbers are the upper readings the
limits are set below.

    python bench/control.py --workload local_hashed.ycsb_c --seeds 1,2,3 \
        --seconds 10

The configuration file's "control" says how to narrow:

  {"key_dtype": "float32", "path": "program"}    the program's own path in
                                                 the lower precision
  {"key_dtype": "bfloat16", "path": "reference"} the plain reference,
                                                 computed in the lower
                                                 precision, in the
                                                 program's place

Prints one JSON line per seed: the seed, `correct` and the checks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NarrowedReference:
    """The plain reference in the lower key precision, behind the facade's
    read and write calls the serving batcher makes."""

    telemetry = None
    snapshot = None

    def __init__(self, keys, vals, key_dtype):
        from .reference import SortedReference
        self._ref = SortedReference(keys, vals, key_dtype=key_dtype)

    def lookup(self, q):
        return self._ref.lookup(q)

    def range(self, lo, hi, max_hits=128):
        return self._ref.range(lo, hi, max_hits)

    def upsert(self, keys, vals):
        self._ref.upsert(keys, vals)

    def delete(self, keys):
        self._ref.delete(keys)

    def items(self):
        return self._ref.items()

    def stats(self) -> dict:
        return {}

    def close(self) -> None:
        pass


def narrowed_dtype(name: str):
    import numpy as np
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def control_kwargs(cfg: dict) -> dict:
    """`run_cell` keyword arguments that put the control in the program's
    place."""
    ctl = cfg["control"]
    if ctl["path"] == "program":
        return dict(key_dtype=ctl["key_dtype"])
    if ctl["path"] == "reference":
        dt = narrowed_dtype(ctl["key_dtype"])
        return dict(make_index=lambda k, v, _cfg: NarrowedReference(k, v, dt))
    raise ValueError(f"unknown control path {ctl['path']!r}")


def run_control(bench: dict, cell: str, seed: int, seconds: float, *,
                bench_dir: str, require_tpu: bool = True, log=None) -> dict:
    from . import harness
    spec = {w["name"]: w for w in bench["workloads"]}[cell]
    cfg = harness.load_data(bench_dir, "configs", spec["config"])
    return harness.run_cell(bench, cell, seed, seconds, False,
                            t_start=time.perf_counter(), bench_dir=bench_dir,
                            require_tpu=require_tpu, log=log,
                            **control_kwargs(cfg))


def main(argv=None) -> int:
    os.environ["JAX_ENABLE_X64"] = "1"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import control, harness
    from repro.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    bench = harness.load_benchmark(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = control.run_control(bench, args.workload, seed,
                                      args.seconds,
                                      bench_dir=harness.BENCH_DIR)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        print(json.dumps(dict(seed=seed, correct=res["correct"],
                              checks=res["checks"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
