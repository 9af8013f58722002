"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals over the window)."""
from bench import trace_reduce


def read(run):
    if run.trace_extract is None:
        return None
    return trace_reduce.idle_share(run.trace_extract)
