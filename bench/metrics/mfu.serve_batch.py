"""mfu.serve_batch: the FLOPs of every served request (``bench/flops.py``: 2·N
per prefilled or decoded token without the head, the head once per row
whose logits are used, attention over the live context) over the engine's
run (net of the profiler's start and stop), the chips and their bf16 peak,
in percent."""
from bench import peaks


def read(record):
    if record.get("job") != "serve_batch" or record.get("platform") != "tpu":
        return None
    peak = peaks.peak(record["device_kind"])["flops_bf16"]
    return 100.0 * record["flops"] / (record["elapsed_s"] * record["chips"]
                                      * peak)
