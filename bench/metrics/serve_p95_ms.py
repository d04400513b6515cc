"""95th percentile of request latency, from each request's due time to its
labels returned, over all requests of the window (ms)."""
import numpy as np

from bench.common import latencies


def read(record):
    if record["kind"] != "serve":
        return None
    return float(np.quantile(latencies(record), 0.95, method="higher") * 1e3)
