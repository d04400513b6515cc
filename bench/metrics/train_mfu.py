"""Required operations of the rows trained (``bench/work.py``) per second
of the window, over chips x the chip's bf16 peak (%)."""
from bench.peaks import peaks


def read(record):
    if record["kind"] != "train" or record["window_s"] <= 0:
        return None
    pk = peaks(record["device"]["kind"])
    rate = record["work"]["ops"] / record["window_s"]
    return 100.0 * rate / (record["chips"] * pk["bf16_flops_per_s"])
