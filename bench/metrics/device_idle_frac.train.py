"""Share of the traced training window in which no operation ran on the
device: 1 - busy / window (%)."""


def read(record):
    tr = record.get("trace")
    if record["kind"] != "train" or not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
