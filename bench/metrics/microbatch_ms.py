"""Median of the serve queue's own per-microbatch latencies, launch to
labels on the host (ms)."""
import numpy as np


def read(record):
    mb = record.get("microbatch_s") or []
    if record["kind"] != "serve" or not mb:
        return None
    return float(np.median(mb) * 1e3)
