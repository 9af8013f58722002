"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload local_hashed.ycsb_c --seed 7 --seconds 10 \
        --trace 0

Prints the cell's end-to-end metrics (`--trace 0`) or its per-layer
metrics read from a profiler trace of the window (`--trace 1`) as one JSON
object on the last line of standard output, with each number the
correctness check compared beside its limit as the last lines of standard
error.  Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.  The compile cache is `.jax_cache/`
in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import os                                                     # noqa: E402
import sys                                                    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# f64 keys need x64 before jax is imported; the compile cache lives in the
# checkout, at a fixed path, whatever the environment said
os.environ["JAX_ENABLE_X64"] = "1"
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    bench = harness.load_benchmark(ROOT)
    from repro.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
