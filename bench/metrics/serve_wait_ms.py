"""95th percentile over the window's requests of the time from ``submit``
to the launch that carries a request's last rows: the program's
``serve.wait`` spans of the newest queue (ms)."""
import numpy as np

from bench import spans


def read(record):
    if record["kind"] != "serve":
        return None
    recs = spans.newest("serve.wait")
    d = None if recs is None else spans.durations_ms(recs, "serve.wait")
    return float(np.quantile(d, 0.95)) if d is not None and d.size else None
