"""Device busy time in the traced window divided by the training steps run
in it (us per step)."""


def read(record):
    tr = record.get("trace")
    if record["kind"] != "train" or not tr or not record["steps"]:
        return None
    return tr["busy_s"] / record["steps"] * 1e6
