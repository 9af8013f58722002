"""99th-percentile end-to-end request latency, over every request of the
window: from the scheduled arrival (open loop) or the submit (closed)."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 99)) * 1e3 if len(lat) else None
