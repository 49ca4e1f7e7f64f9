#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--seconds 10] [--out <file.jsonl>]

For every seed, the program's readings: what a run compares (the job's
own set-up and timed path at the cell's sizes; serving at the cell's load
for ``--seconds``). For the control seeds also the control's readings, the
plain reference put in the program's place at the precision below the
stated one (float8 products for bfloat16 compute), and those of the faults
a cell can have, planted in the reference put in the program's place:
training, half of each batch left out; serving, each served token altered
(a step that returns its state unchanged reads 1 on ``update_norm_gap`` by
definition and needs no run). One process holds the chip for all seeds.
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def train_readings(ctx, control: bool) -> dict:
    from bench import checks
    from bench.jobs import train

    b = train.build(ctx)
    state, _, prog = train.first_steps(ctx, b)
    del state
    b["gym"] = None
    prog["update_norms"] = train.program_update_norms(
        ctx, prog.pop("params_after"))
    ref = train.reference_readings(ctx, b["rows"])
    out = {"program": checks.train_readings(prog, ref)}
    if control:
        ctl = train.reference_readings(ctx, b["rows"], mode="fp8")
        out["control"] = checks.train_readings(ctl, ref)
        half = train.reference_readings(ctx, b["rows"], half_batch=True)
        out["half_batch"] = checks.train_readings(half, ref)
    return out


def serve_readings(ctx, control: bool) -> dict:
    from bench import serving

    engine = serving.build(ctx)
    requests = serving.requests_of(ctx)
    engine._warmup([r.prompt_len for r in requests])
    res = serving.window(ctx, engine, requests,
                         realtime=ctx.spec["job"] == "serve_online")
    del engine
    prompts = {r.rid: r.prompt for r in requests}
    picked = serving.sample(ctx, res["requests"], prompts)
    modes = ("f32", "fp8") if control else ("f32",)
    served, others = serving.gaps(ctx, picked, modes, altered=control)
    n = sum(len(g) for g in served)
    out = {"program": {"logit_gap": (max(float(g.max()) for g in served),
                                     f"{n} tokens")}}
    if control:
        out["control"] = {"logit_gap": (max(float(g.max())
                                            for g in others["fp8"]), "")}
        out["token_altered"] = {"logit_gap": (max(
            float(g.max()) for g in others["altered"]), "")}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    devices = harness.take_chips(bench, args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(controls - set(seeds)):
        t0 = time.perf_counter()
        ctx = harness.Run(ROOT, args.workload, seed, args.seconds, False,
                          time.perf_counter(), devices)
        kind = ctx.spec["job"]
        fn = train_readings if kind == "train" else serve_readings
        out = fn(ctx, seed in controls)
        line = {"workload": args.workload, "seed": seed,
                "in_program_seeds": seed in seeds,
                "seconds": round(time.perf_counter() - t0, 1),
                **{who: {k: v[0] for k, v in r.items()}
                   for who, r in out.items()},
                "where": {who: {k: v[1] for k, v in r.items()}
                          for who, r in out.items()}}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
