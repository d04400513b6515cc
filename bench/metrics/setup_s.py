"""Set-up seconds: process start to the first timed step (data, shards,
bank, warm-up and compilation of this cell's own shapes)."""


def read(record):
    return record["setup_s"]
