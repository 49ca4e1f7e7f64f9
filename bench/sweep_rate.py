#!/usr/bin/env python3
"""Find the request rate a serving cell's engine sustains, once, on the
chip; the cell's traffic file then fixes its rate as a number.

    python3 bench/sweep_rate.py --workload <cell> --rates 3,4,5,6 \
        [--seconds 30] [--seed 1]

One engine is built and warmed up; the cell's mix is then offered at each
rate for ``--seconds`` (open loop for an online cell, all at t = 0 for a
batch cell). Per rate it prints the completions per second, TTFT and
per-token tails, and the TTFT of the first and the last third of the
arrivals: a backlog that grows through the run shows as a last third far
slower than the first. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, serving  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    devices = harness.take_chips(bench, args.workload)
    ctx = harness.Run(ROOT, args.workload, args.seed, args.seconds, False,
                      T_START, devices)
    online = ctx.spec["job"] == "serve_online"
    engine = serving.build(ctx)
    engine._warmup([1])
    for rate in [float(r) for r in args.rates.split(",")]:
        ctx.traffic = dict(ctx.traffic, rate_per_s=rate)
        requests = serving.requests_of(ctx)
        res = engine.run(requests, realtime=online, warmup=False)
        rows = sorted(res["requests"], key=lambda r: r["arrival_s"])
        ttft = np.array([r["ttft_s"] for r in rows])
        tpot = np.array([1000 * (r["done_s"] - r["arrival_s"] - r["ttft_s"])
                         / (r["n_gen"] - 1) for r in rows if r["n_gen"] > 1])
        third = max(1, len(rows) // 3)
        out = {"rate": rate, "requests": len(rows),
               "elapsed_s": res["elapsed_s"],
               "completed_per_s": len(rows) / res["elapsed_s"],
               "output_tokens_per_s": sum(r["n_gen"] for r in rows)
               / res["elapsed_s"],
               "ttft_p50_s": float(np.percentile(ttft, 50)),
               "ttft_p95_s": float(np.percentile(ttft, 95)),
               "tpot_p95_ms": float(np.percentile(tpot, 95)),
               "ttft_first_third_p50_s": float(np.median(ttft[:third])),
               "ttft_last_third_p50_s": float(np.median(ttft[-third:])),
               "tick_ms": 1000 * res["decode_s"] / max(res["ticks"], 1),
               "slot_utilization": res["slot_utilization"]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
