"""Mean device-to-host copy of one checkpoint in the window's trainer call:
the program's ``ckpt.copy`` spans under the newest ``fit.stream`` root
(ms)."""
from bench import spans


def read(record):
    if record["kind"] != "train":
        return None
    recs = spans.newest("fit.stream")
    d = None if recs is None else spans.durations_ms(recs, "ckpt.copy")
    return float(d.mean()) if d is not None and d.size else None
