"""Padded rows over all rows the serve queue scored, from the queue's own
counters (%)."""


def read(record):
    if record["kind"] != "serve":
        return None
    st = record["stats"]
    scored = st["rows"] + st["padded_rows"]
    return 100.0 * st["padded_rows"] / scored if scored else None
