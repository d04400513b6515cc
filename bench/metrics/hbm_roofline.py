"""The least time a training step could take on the chip (the larger of
its required operations over peak and its required bytes over HBM
bandwidth, ``bench/work.py``, as ``step_roofline`` counts it), over the
device time per step of ``train_step_hbm_pallas`` (``hbm_step_us``) (%)."""
from bench import common
from bench.peaks import peaks


def read(record):
    w = record.get("work")
    per_step_us = common.load_metric("hbm_step_us").read(record)
    if not per_step_us or not w or not w["steps"]:
        return None
    pk = peaks(record["device"]["kind"])
    t_ops = w["ops"] / w["steps"] / pk["bf16_flops_per_s"]
    t_bytes = w["bytes"] / w["steps"] / pk["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "operations"
    print(f"hbm_roofline: bound by {bound} ({t_ops * 1e6:.6g} us of "
          f"operations, {t_bytes * 1e6:.6g} us of bytes per step; "
          f"train_step_hbm_pallas {per_step_us:.6g} us)", flush=True)
    return 100.0 * max(t_ops, t_bytes) / (per_step_us * 1e-6)
