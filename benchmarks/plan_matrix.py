"""Per-plan bench rows for the composed-parallelism matrix.

Runs ``Gym.bench`` (the one timing implementation) once per sharding
plan on a forced-8-device CPU mesh and writes one row per plan —
``steady_step_ms`` / ``tokens_per_s`` (and ``mfu`` on a device with a
peak-table entry; never on the CPU) plus the analytic
pipeline block (``pp``, ``n_micro``, ``bubble_fraction``) — into the
tracked ``BENCH_plans.json`` at the repo root.  Absolute CPU numbers
are meaningless as GPU/TPU predictors; the row set exists so every
composed plan has a *working, timed* configuration that future PRs
re-run and diff structurally (plan string, bubble math, shard
warnings), and so relative regressions within one matrix refresh are
visible.

    PYTHONPATH=src python benchmarks/plan_matrix.py [--steps 12] [--out ...]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import dataclasses
import json
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# (plan, arch, mesh kwargs) — every composed plan in the catalog that an
# 8-device host mesh can realize, dense and MoE
MATRIX = [
    ("ddp", "qwen1p5_0p5b", dict(dp=8, tp=1)),
    ("fsdp", "qwen1p5_0p5b", dict(dp=8, tp=1)),
    ("fsdp_tp", "qwen1p5_0p5b", dict(dp=4, tp=2)),
    ("pp2_fsdp", "qwen1p5_0p5b", dict(dp=4, tp=1, pp=2)),
    ("pp2_fsdp_tp", "qwen1p5_0p5b", dict(dp=2, tp=2, pp=2)),
    ("fsdp_tp_ep", "deepseek_moe_16b", dict(dp=4, tp=2)),
    ("pp2_fsdp_tp_ep", "deepseek_moe_16b", dict(dp=2, tp=2, pp=2)),
]


def build_arch(arch: str):
    from repro.configs import get_reduced

    cfg = get_reduced(arch)
    if cfg.moe:
        # 4 layers so both the dense prelude and the MoE stack split into
        # 2 contiguous stages (reduced default is 2 layers / 1 dense)
        return dataclasses.replace(
            cfg, n_layers=4,
            moe=dataclasses.replace(cfg.moe, n_dense_layers=2))
    return cfg


def bench_plan(plan_name: str, arch: str, mesh_kw, steps: int, warmup: int,
               global_batch: int = 8):
    import repro.core.components  # noqa: F401  (populate the registry)
    from repro.config.registry import DEFAULT_REGISTRY as REG
    from repro.core.gym import Gym
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model
    from repro.optim.adamw import AdamW
    from repro.sharding import plans as PL

    cfg = build_arch(arch)
    model = build_model(cfg)
    ds = REG.build("dataset", "synthetic", n_tokens=60000, vocab=cfg.vocab,
                   prefix=f"/tmp/repro_plan_matrix_{arch}", seq_len=64,
                   seed=0)
    loader = REG.build("loader", "sharded", dataset=ds,
                       global_batch=global_batch)
    gym = Gym(model=model, optimizer=AdamW(lr=1e-3), loader=loader,
              mesh=make_local_mesh(**mesh_kw), plan=PL.make_plan(plan_name),
              log_every=0, prefetch=2)
    res = gym.bench(steps=steps, warmup=warmup)
    row = {
        "plan_name": plan_name,
        "arch": cfg.name,
        "mesh": "x".join(f"{k}={v}" for k, v in mesh_kw.items()),
        "n_layers": cfg.n_layers,
    }
    for k in ("platform", "device_kind", "device_count",
              "plan", "pipeline", "compile_s", "steady_step_ms",
              "steady_step_ms_mean", "mfu", "tokens_per_s", "final_loss",
              "global_batch", "seq_len"):
        if k in res:
            row[k] = res[k]
    row["shard_warnings"] = list(getattr(gym, "shard_warnings", []) or [])
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_plans.json"))
    ap.add_argument("--only", default="",
                    help="comma-separated plan names (default: all)")
    args = ap.parse_args(argv)

    only = {p for p in args.only.split(",") if p}
    rows = []
    for plan_name, arch, mesh_kw in MATRIX:
        if only and plan_name not in only:
            continue
        print(f"== {plan_name} ({arch}) ==", flush=True)
        row = bench_plan(plan_name, arch, mesh_kw, args.steps, args.warmup)
        print(json.dumps({k: row[k] for k in
                          ("plan", "steady_step_ms", "mfu", "pipeline")
                          if k in row}), flush=True)
        rows.append(row)

    out = {"devices": 8, "steps": args.steps, "warmup": args.warmup,
           "rows": rows}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {len(rows)} rows -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
