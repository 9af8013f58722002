"""Window deltas of the program's span summaries (`LearnedIndex.metrics()`
"spans", taken at the window's start and after the drain), for the
readers under `metrics/`.  Each returns None where the program has no
such span."""


def count(run, name: str) -> int | None:
    a, b = run.spans.get(name), run.spans_before.get(name)
    if a is None or b is None:
        return None
    return a["count"] - b["count"]


def total_ms(run, name: str) -> float | None:
    a, b = run.spans.get(name), run.spans_before.get(name)
    if a is None or b is None:
        return None
    return a["ms_mean"] * a["count"] - b["ms_mean"] * b["count"]


def per_batch(run, x: float | None) -> float | None:
    """`x` over the batches the batcher dispatched in the window."""
    n = (run.serve_after.get("n_batches", 0)
         - run.serve_before.get("n_batches", 0))
    return None if x is None or n <= 0 else x / n
