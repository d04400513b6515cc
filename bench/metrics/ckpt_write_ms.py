"""Mean write of one checkpoint in the window's trainer call (``savez``,
crc32, fsync, rename and clean-up): the program's ``ckpt.write`` spans
under the newest ``fit.stream`` root (ms)."""
from bench import spans


def read(record):
    if record["kind"] != "train":
        return None
    recs = spans.newest("fit.stream")
    d = None if recs is None else spans.durations_ms(recs, "ckpt.write")
    return float(d.mean()) if d is not None and d.size else None
