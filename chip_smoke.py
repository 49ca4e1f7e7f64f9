#!/usr/bin/env python3
"""Smoke test of the main path on a TPU at qwen1.5-0.5b's published widths.

    python3 chip_smoke.py            # one chip: train, serve, kernels
    python3 chip_smoke.py --chips 4  # fsdp_tp on a (2, 2) mesh vs one chip

Everything runs in this one process, which holds the chip for the whole
run. Training and serving go through the run API (``execute_doc``, the
path behind ``python -m repro bench|serve``) with random weights from a
seed and synthetic tokens generated under ``results/chip_smoke/``. Each
phase checks its outputs against the repo's own references and raises on a
mismatch; a failed phase, or a machine without a TPU, ends the script with
a non-zero exit and no result line. The times printed are one-chip smoke
observations, not benchmark numbers.

The last line of standard output is the only JSON result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
OUT = os.path.join(ROOT, "results", "chip_smoke")

# The configuration: qwen1.5-0.5b at its published widths and full depth.
# A CPU rehearsal at tiny size rebinds these names (reduced config, its
# vocabulary and parameter count, a short sequence).
ARCH = "qwen1p5_0p5b"
ARCH_CONFIG = {"reduced": False}
VOCAB = 151936
N_PARAMS = 463_987_712          # tied embeddings, QKV bias
SEQ_LEN = 1024
# the largest batch that fits with room to spare: compiled for a v5e, the
# step needs 5.19 GiB of state plus 8.48 GiB of temporaries at 12; at 16
# it needs 15.74 GiB, the whole of the chip's HBM
GLOBAL_BATCH = 12
# the serve workload: greedy requests sharing one system prompt
N_REQUESTS, PREFIX_LEN, TAIL_LEN, GEN_TOKENS = 8, 64, 16, 32
# per-step loss agreement between the (2, 2) fsdp_tp mesh and one chip:
# bf16 activations reduced in another order move the loss by ~1e-3
LOSS_ATOL_4CHIP = 2e-2


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# run documents (the same grammar as the YAML under examples/configs/)
# ---------------------------------------------------------------------------
def _arch():
    return {
        "arch": {"component_key": "arch_config", "variant_key": ARCH,
                 "config": dict(ARCH_CONFIG)},
        "model": {"component_key": "model", "variant_key": "auto",
                  "config": {"arch_config": {"instance_key": "arch"}}},
    }


def bench_doc(name, *, steps, warmup, windows, profile=None, mesh=None):
    """A ``bench`` run: AdamW, the sharded loader (the gym adds device
    prefetch), seeded synthetic tokens; ``mesh`` = (dp, tp) runs fsdp_tp."""
    gym = {"model": {"instance_key": "model"},
           "optimizer": {"instance_key": "optimizer"},
           "loader": {"instance_key": "loader"},
           "seed": 0, "prefetch": 2}
    doc = {
        "run": {"kind": "bench", "name": name,
                "output_dir": os.path.join(OUT, name),
                "bench": {"steps": steps, "warmup": warmup,
                          "windows": windows, "bench_dir": "",
                          "telemetry": {"profile": profile} if profile
                          else {}}},
        **_arch(),
        "optimizer": {"component_key": "optimizer", "variant_key": "adamw",
                      "config": {"lr": 1e-3}},
        "dataset": {"component_key": "dataset", "variant_key": "synthetic",
                    "config": {"n_tokens": 1 << 20, "vocab": VOCAB,
                               "prefix": os.path.join(OUT, "data", "tokens"),
                               "seq_len": SEQ_LEN, "seed": 0}},
        "loader": {"component_key": "loader", "variant_key": "sharded",
                   "config": {"dataset": {"instance_key": "dataset"},
                              "global_batch": GLOBAL_BATCH}},
        "gym": {"component_key": "gym", "variant_key": "standard",
                "config": gym},
    }
    if mesh is not None:
        dp, tp = mesh
        doc["mesh"] = {"component_key": "mesh_provider", "variant_key": "local",
                       "config": {"dp": dp, "tp": tp}}
        doc["plan"] = {"component_key": "sharding_plan",
                       "variant_key": "fsdp_tp"}
        gym["mesh_provider"] = {"instance_key": "mesh"}
        gym["sharding_plan"] = {"instance_key": "plan"}
    return doc


def serve_doc(name, *, prefix_cache):
    """A ``serve`` run: the continuous-batching engine over a paged KV cache,
    greedy requests sharing one prefix, closed loop."""
    return {
        "run": {"kind": "serve", "name": name,
                "output_dir": os.path.join(OUT, name),
                "serve": {
                    "engine": True, "n_slots": N_REQUESTS,
                    "max_len": PREFIX_LEN + TAIL_LEN + GEN_TOKENS,
                    "eos_id": -1, "block_len": 16, "prefill_chunk": 32,
                    "prefix_cache": prefix_cache,
                    "sampling": {"temperature": 0.0},
                    "workload": {"n_requests": N_REQUESTS, "rate": 0.0,
                                 "prefix_len": PREFIX_LEN, "n_prefixes": 1,
                                 "prompt_lens": [TAIL_LEN],
                                 "gen_tokens": [GEN_TOKENS], "seed": 0,
                                 "realtime": False},
                    "compare_static": False, "bench_dir": ""}},
        **_arch(),
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def device_phase(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU found: {e}") from None
    d = devices[0]
    log(f"device: platform {d.platform}, device_kind {d.device_kind!r}, "
        f"count {len(devices)}")
    check(d.platform == "tpu",
          f"no TPU found: JAX's first device is on {d.platform!r}; this "
          f"script measures the chip and has no CPU fallback")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    return devices


def trace_has_tpu_plane(trace_dir: str) -> bool:
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    check(bool(files), f"no profiler trace under {trace_dir}")
    planes = list(jax.profiler.ProfileData.from_file(files[0]).planes)
    log(f"trace planes: {[p.name for p in planes]}")
    return any(p.name.startswith("/device:TPU") and list(p.lines)
               for p in planes)


def train_phase():
    """Training through the bench kind: one compile step, four warm-up
    steps (steps 2-3 traced), eight timed steps."""
    import jax

    from repro.run.api import execute_doc

    res = execute_doc(bench_doc("train", steps=8, warmup=4, windows=4,
                                profile={"start_step": 2, "num_steps": 2}))
    losses = res["losses"]
    log(f"train: {res['n_params']:,} parameters, batch {GLOBAL_BATCH} x "
        f"seq {SEQ_LEN}, losses {[round(x, 4) for x in losses]}")
    check(res["n_params"] == N_PARAMS,
          f"parameter count {res['n_params']:,} != {N_PARAMS:,}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    # uniform random tokens: the first loss is ln(vocab) plus about half
    # the variance of the random-init logits
    ln_v = math.log(VOCAB)
    check(abs(losses[0] - ln_v) < 0.25,
          f"first loss {losses[0]:.4f} is not near ln(vocab) = {ln_v:.4f}")
    trace = res.get("profile_trace")
    check(bool(trace), "the profile window wrote no trace")
    check(trace_has_tpu_plane(trace), f"trace {trace} has no TPU device plane")
    log(f"train: profiler trace with a TPU device plane at {trace}")
    peak = res.get("peak_bytes_in_use")
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    gib = lambda b: None if b is None else round(b / 2**30, 3)  # noqa: E731
    log(f"train (one-chip smoke observation, not a benchmark): compile "
        f"{res['compile_s']} s, steady {res['steady_step_ms']} ms/step, "
        f"{res.get('tokens_per_s')} tok/s, mfu {res.get('mfu')}, "
        f"peak_bytes_in_use {gib(peak)} GiB of {gib(limit)} GiB")


def serve_phase():
    """The engine serves greedy requests on one shared prefix; every one
    gets its budget, and the streams are identical with the prefix cache
    on and off (hit == cold)."""
    from repro.run.api import execute_doc

    streams = {}
    for cache in (True, False):
        res = execute_doc(serve_doc(f"serve_cache_{'on' if cache else 'off'}",
                                    prefix_cache=cache))
        rows = res["requests"]
        check(res["completed"] == len(rows) == N_REQUESTS,
              f"completed {res['completed']} of {len(rows)} requests")
        check(all(r["finish"] == "length"
                  and r["n_gen"] == r["max_new"] == GEN_TOKENS for r in rows),
              f"a request stopped short of its budget: "
              f"{[(r['id'], r['finish'], r['n_gen']) for r in rows]}")
        if cache:
            check(res["prefill_cache_hit_rate"] > 0,
                  "the shared prefix never hit the prefix cache")
        streams[cache] = {r["id"]: r["gen_ids"] for r in rows}
        log(f"serve (prefix_cache={cache}): {res['completed']} requests "
            f"done, hit rate {res['prefill_cache_hit_rate']}, compile "
            f"{res['compile_s']} s, ttft p50 {res['ttft_s'].get('p50')} s, "
            f"tpot p50 {res['tpot_ms'].get('p50')} ms (smoke observation)")
    check(streams[True] == streams[False],
          "token streams differ between prefix cache on and off")
    log("serve: token streams identical with prefix cache on and off")


def kernel_phase():
    """Each Pallas kernel once, compiled, at the widths of a model that uses
    it, against its reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.flash.ops import flash_attention
    from repro.kernels.flash.ref import attention_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_recurrence_ref

    def run(name, fn, ref_fn, args, tol):
        jitted = jax.jit(fn)
        hlo = jitted.lower(*args).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"{name}: the compiled program holds no Mosaic kernel")
        out = np.asarray(jitted(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(ref_fn)(*args), np.float32)
        err = float(np.max(np.abs(out - ref)))
        log(f"kernel {name}: max |kernel - reference| {err:.3e} "
            f"(tolerance {tol:.3e})")
        check(np.isfinite(out).all() and err <= tol,
              f"{name}: kernel disagrees with its reference")

    cfg = get_config("qwen1p5_0p5b")
    B, S, H, K, dh = 4, 2048, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, dh), jnp.bfloat16)
    run("flash forward (qwen1.5-0.5b widths)",
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        lambda q, k, v: attention_ref(q, k, v, causal=True),
        (q, k, v), 2.5e-2)

    cfg = get_config("mamba2_780m")
    ssm = cfg.ssm
    Hs = ssm.expand * cfg.d_model // ssm.head_dim
    Bs, Ss, P, G, N = 2, 2048, ssm.head_dim, ssm.n_groups, ssm.d_state
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (Bs, Ss, Hs, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bs, Ss, Hs)))
    A = -jnp.exp(jax.random.normal(ks[2], (Hs,)) * 0.5)
    Bm = jax.random.normal(ks[3], (Bs, Ss, G, N), jnp.bfloat16) * 0.3
    Cm = jax.random.normal(ks[4], (Bs, Ss, G, N), jnp.bfloat16) * 0.3
    D = jnp.ones((Hs,))
    ref_scale = float(jnp.max(jnp.abs(
        ssd_recurrence_ref(x, dt, A, Bm, Cm, D).astype(jnp.float32))))
    run(f"ssd scan (mamba2-780m widths: {Hs} heads, P={P}, N={N}, "
        f"chunk {ssm.chunk})",
        lambda *a: ssd(*a, chunk=ssm.chunk), ssd_recurrence_ref,
        (x, dt, A, Bm, Cm, D), 3e-2 * ref_scale)


def four_chip_phase():
    """The same seeded steps on one chip and under fsdp_tp on a (2, 2)
    mesh: per-step losses agree and the state is spread over all four."""
    from repro.run.api import execute_doc

    one = execute_doc(bench_doc("one_chip", steps=6, warmup=1, windows=2))
    four = execute_doc(bench_doc("fsdp_tp_2x2", steps=6, warmup=1, windows=2,
                                 mesh=(2, 2)))
    check(one["device_count"] == 1 and four["device_count"] == 4,
          f"device counts {one['device_count']} / {four['device_count']}")
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    log(f"4-chip: one-chip losses {[round(x, 5) for x in one['losses']]}")
    log(f"4-chip: fsdp_tp (2, 2) losses "
        f"{[round(x, 5) for x in four['losses']]}")
    log(f"4-chip: max per-step |difference| {max(diffs):.3e} "
        f"(tolerance {LOSS_ATOL_4CHIP})")
    check(len(diffs) == len(one["losses"]) == len(four["losses"]) > 0,
          "the two runs took different numbers of steps")
    check(all(math.isfinite(x) for x in one["losses"] + four["losses"]),
          "non-finite loss")
    check(max(diffs) <= LOSS_ATOL_4CHIP,
          "fsdp_tp losses disagree with the one-chip losses")
    per_dev = four["state_bytes_per_device"]
    total = four["state_bytes"]
    log(f"4-chip: params + optimizer state {total / 2**30:.3f} GiB, per "
        f"device {[round(b / 2**30, 3) for b in per_dev.values()]} GiB")
    check(len(per_dev) == 4 and all(b > 0 for b in per_dev.values()),
          f"state is not on all four devices: {per_dev}")
    check(max(per_dev.values()) < total / 2,
          "a device holds more than half of the state: it is not sharded")
    for r, label in ((one, "one chip"), (four, "fsdp_tp (2, 2)")):
        log(f"4-chip ({label}, smoke observation, not a benchmark): compile "
            f"{r['compile_s']} s, steady {r['steady_step_ms']} ms/step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only fsdp_tp on a (2, 2) mesh against one chip")
    args = ap.parse_args(argv)

    from repro.run.cli import use_compile_cache

    devices = device_phase(args.chips)
    log(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        train_phase()
        serve_phase()
        kernel_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
