"""mfu.train: model FLOPs of the window's steps (``bench/flops.py``: 6·N·D,
causal attention scores, SSD chunk terms; no recomputation) over the
window, the chips and their bf16 peak (``bench/peaks.py``), in percent."""
from bench import peaks


def read(record):
    if record.get("job") != "train" or record.get("platform") != "tpu":
        return None
    peak = peaks.peak(record["device_kind"])["flops_bf16"]
    return 100.0 * record["flops"] / (record["window_s"] * record["chips"]
                                      * peak)
