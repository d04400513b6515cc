"""Device time per training step of the fused kernel that keeps the kernel
cache in HBM: the traced window's operations named
``train_step_hbm_pallas*`` over the steps run in it (us).  A program
without that kernel has no such operation, and the metric is left out."""

KERNEL = "train_step_hbm_pallas"


def read(record):
    tr = record.get("trace")
    if record["kind"] != "train" or not tr or not record["steps"]:
        return None
    secs = sum(t for name, t in tr["device_ops"] if name.startswith(KERNEL))
    return secs / record["steps"] * 1e6 if secs else None
