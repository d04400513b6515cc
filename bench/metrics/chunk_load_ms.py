"""Mean host time of one chunk load in the window (shard read), timed by
the benchmark's wrapping chunk source (ms)."""


def read(record):
    loads = record.get("chunk_load_s") or []
    if not loads:
        return None
    return sum(loads) / len(loads) * 1e3
