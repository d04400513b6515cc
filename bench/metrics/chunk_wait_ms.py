"""Mean time the trainer waited for its next staged chunk, over the
window's chunks after the first (the base of ``chunk_gap_ms``): the
program's ``stream.wait`` spans under the newest ``fit.stream`` root
(ms)."""
from bench import spans


def read(record):
    if record["kind"] != "train":
        return None
    recs = spans.newest("fit.stream")
    d = None if recs is None else spans.durations_ms(recs, "stream.wait")
    return float(d[1:].mean()) if d is not None and d.size > 1 else None
