"""Rows trained per second: rows of every chunk program that completed in
the window, over the window's wall time to the last chunk's completion."""


def read(record):
    if record["kind"] != "train" or record["window_s"] <= 0:
        return None
    return record["rows"] / record["window_s"]
