"""Mean device idle time between consecutive chunk programs in the traced
window: from the end of one to the start of the next (ms)."""


def read(record):
    tr = record.get("trace") or {}
    gaps = tr.get("program_gaps_s") or []
    if record["kind"] != "train" or not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3
