"""The least time a training step could take on the chip (the larger of
its required operations over peak and its required bytes over HBM
bandwidth, ``bench/work.py``), over the device time per step (%)."""
from bench.peaks import peaks


def read(record):
    tr = record.get("trace")
    w = record.get("work")
    if record["kind"] != "train" or not tr or not w or not w["steps"]:
        return None
    pk = peaks(record["device"]["kind"])
    t_ops = w["ops"] / w["steps"] / pk["bf16_flops_per_s"]
    t_bytes = w["bytes"] / w["steps"] / pk["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "operations"
    device = tr["busy_s"] / w["steps"]
    print(f"step_roofline: bound by {bound} ({t_ops * 1e6:.6g} us of "
          f"operations, {t_bytes * 1e6:.6g} us of bytes per step; device "
          f"{device * 1e6:.6g} us)", flush=True)
    return 100.0 * max(t_ops, t_bytes) / device
