"""Median request latency over the same requests as ``serve_p95_ms`` (ms)."""
import numpy as np

from bench.common import latencies


def read(record):
    if record["kind"] != "serve":
        return None
    return float(np.quantile(latencies(record), 0.5, method="higher") * 1e3)
