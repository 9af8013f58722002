"""Ops per coalesced facade batch in the window (batcher `stats()`)."""


def read(run):
    b0, b1 = run.serve_before, run.serve_after
    n = b1.get("n_batches", 0) - b0.get("n_batches", 0)
    if n <= 0:
        return None
    return (b1["completed_ops"] - b0["completed_ops"]) / n
