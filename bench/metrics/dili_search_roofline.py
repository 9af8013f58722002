"""The Pallas search kernel's share of its roofline: the bytes the DILI
search must read for the lookups served (`roofline.bytes_per_query`), at
the chip's peak HBM bandwidth, over the kernel's device time.  The search
does no arithmetic worth counting, so bandwidth bounds it."""
from bench import roofline, trace_reduce

KERNEL = "dili_search"


def read(run):
    ex, n = run.trace_extract, run.lookup_ops()
    if ex is None or not n or run.bytes_per_query is None:
        return None
    t_s = trace_reduce.time_ns(ex, "ops", KERNEL) * 1e-9
    if t_s <= 0:
        return None
    least_s = (run.bytes_per_query * n
               / roofline.peaks(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / t_s
