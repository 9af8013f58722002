"""Median end-to-end request latency, over every request of the window."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 50)) * 1e3 if len(lat) else None
