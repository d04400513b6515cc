"""95th percentile over the window's requests of the time from a request's
labels being scattered to ``take`` returning them to the caller: the
program's ``serve.handoff`` spans of the newest queue (ms)."""
import numpy as np

from bench import spans


def read(record):
    if record["kind"] != "serve":
        return None
    recs = spans.newest("serve.wait")
    d = None if recs is None else spans.durations_ms(recs, "serve.handoff")
    return float(np.quantile(d, 0.95)) if d is not None and d.size else None
