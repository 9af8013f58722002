"""Device time of the XLA lookup executable (`search_with_overlay`) in the
traced window, over the lookups served."""
from bench import trace_reduce

EXECUTABLE = "search_with_overlay"


def read(run):
    ex, n = run.trace_extract, run.lookup_ops()
    if ex is None or not n:
        return None
    t = trace_reduce.time_ns(ex, "modules", EXECUTABLE)
    return t / n if t > 0 else None
