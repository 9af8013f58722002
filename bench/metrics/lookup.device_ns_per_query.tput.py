"""Device time of the Pallas search kernel's events in the traced window,
over the lookups served."""
from bench import trace_reduce

KERNEL = "dili_search"


def read(run):
    ex, n = run.trace_extract, run.lookup_ops()
    if ex is None or not n:
        return None
    t = trace_reduce.time_ns(ex, "ops", KERNEL)
    return t / n if t > 0 else None
