"""Static-batch serving shim — routed through the continuous-batching
engine (:mod:`repro.serve`).

This is the compatibility surface for the original benchmark: ``batch``
identical greedy requests admitted at once into ``batch`` slots, one
generation each — numerics-identical to the old host-looped prefill+argmax
path (tested).  The engine path (``run.serve.engine: true`` — sampling,
EOS stopping, Poisson workloads, mid-flight admission) lives in
``repro/serve/``; see the README "Serving" section.

Run API (preferred):

  PYTHONPATH=src python -m repro serve --config examples/configs/serve.yaml

Deprecated flag shim (delegates through the same Run API):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --prompt-len 32 --gen 16 --batch 4
"""
from __future__ import annotations

import argparse
import sys
from typing import Any, Callable, Dict, Optional


def serve_benchmark(model, *, batch: int = 4, prompt_len: int = 32,
                    gen: int = 16, ckpt: str = "", seed: int = 0,
                    params: Any = None, mesh: Any = None, plan: Any = None,
                    log: Optional[Callable[[str], None]] = None) -> Dict[str, Any]:
    """Prefill + greedy-decode a resolved model; returns throughput metrics.

    The model is a resolved ``model`` component; ``ckpt`` optionally
    restores trained params (params-only, from a full TrainState checkpoint
    in either the sharded-dir or legacy npz format); ``mesh``/``plan``
    shard the serve exactly like the engine path (so an engine-vs-shim
    comparison stays equal-footing).  Token accounting: every request
    generates ``gen`` tokens — the first is sampled from the prefill
    logits (counted in ``prefill_s``/TTFT), the remaining ``gen - 1`` are
    decode ticks (``decode_tok_s`` covers exactly those).  Per-request
    streams come back for ALL rows in ``generated_ids``.
    """
    import jax

    from ..serve.engine import ServeEngine, load_params
    from ..serve.workload import static_trace

    log = log or (lambda msg: print(msg, flush=True))
    cfg = model.cfg
    if params is None:
        params = load_params(model, ckpt=ckpt, seed=seed)
    B, P, G = int(batch), int(prompt_len), int(gen)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, P), 3,
                                 cfg.vocab)
    if cfg.arch_type == "audio" or cfg.n_patches:
        return _multimodal_benchmark(model, params, prompts, G, log)
    # block_len=0 pins the dense slot pool: this shim's contract is bitwise
    # identity with the pre-engine host loop, and the paged chunk-prefill
    # program is a different fused computation
    engine = ServeEngine(model, params, n_slots=B, max_len=P + G,
                         mesh=mesh, plan=plan, greedy=True, block_len=0)
    trace = static_trace(jax.device_get(prompts), G, seed=seed)
    out = engine.run(trace, realtime=False)

    rows = out["requests"]
    t_prefill, t_decode = out["prefill_s"], out["decode_s"]
    res = {
        "arch": cfg.name,
        "batch": B,
        "prompt_len": P,
        "gen": G,
        "prefill_s": round(t_prefill, 3),
        "prefill_tok_s": int(B * P / max(t_prefill, 1e-9)),
        "decode_s": round(t_decode, 3),
        "decode_steps": G - 1,
        "decode_tokens": out["decode_tokens"],
        "decode_tok_s": out["decode_tok_s"],
        "tick_ms": out["tick_ms"],
        "gen_tokens_total": out["generated_tokens"],
        "generated_ids": [r["gen_ids"] for r in rows],
        "generated_ids_0": rows[0]["gen_ids"] if rows else [],
    }
    log(f"prefill: {B}x{P} tokens in {t_prefill:.3f}s "
        f"({res['prefill_tok_s']} tok/s, first token of each request "
        f"sampled here)")
    log(f"decode:  {B}x{G - 1} tokens in {t_decode:.3f}s "
        f"({res['decode_tok_s']} tok/s)")
    log(f"generated ids[0]: {res['generated_ids_0']}")
    return res


def _multimodal_benchmark(model, params, prompts, gen: int,
                          log: Callable[[str], None]) -> Dict[str, Any]:
    """Audio/VLM static path: the slot scheduler carries no modality extras,
    so these archs keep the direct host-looped greedy benchmark (same
    accounting conventions as the engine-routed text path)."""
    import time

    import jax
    import jax.numpy as jnp

    from ..train import steps as ST

    cfg = model.cfg
    B, P = prompts.shape
    G = int(gen)
    max_len = P + G
    batch_in: Dict[str, Any] = {"tokens": prompts}
    if cfg.arch_type == "audio":
        batch_in["frames"] = jnp.zeros((B, cfg.encoder_frames, cfg.d_model))
    if cfg.n_patches:
        batch_in["patch_embeds"] = jnp.zeros((B, cfg.n_patches, cfg.d_model))

    t0 = time.time()
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))
    logits, cache = prefill(params, batch_in)
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(tokens)
    t_prefill = time.time() - t0

    serve_step = jax.jit(ST.make_serve_step(model), donate_argnums=(1,))
    generated = [tokens]
    t0 = time.time()
    for i in range(G - 1):
        pos = jnp.full((B,), P + i, jnp.int32)
        tokens, _, cache = serve_step(params, cache, tokens, pos)
        generated.append(tokens)
    jax.block_until_ready(tokens)
    t_decode = time.time() - t0
    gen_ids = jax.device_get(jnp.stack(generated, axis=1))

    res = {
        "arch": cfg.name,
        "batch": B,
        "prompt_len": P,
        "gen": G,
        "prefill_s": round(t_prefill, 3),
        "prefill_tok_s": int(B * P / max(t_prefill, 1e-9)),
        "decode_s": round(t_decode, 3),
        "decode_steps": G - 1,
        "decode_tokens": B * (G - 1),
        "decode_tok_s": int(B * (G - 1) / max(t_decode, 1e-9)),
        "gen_tokens_total": B * G,
        "generated_ids": [row.tolist() for row in gen_ids],
        "generated_ids_0": gen_ids[0].tolist(),
    }
    log(f"prefill: {B}x{P} tokens in {t_prefill:.3f}s "
        f"({res['prefill_tok_s']} tok/s)")
    log(f"decode:  {B}x{G - 1} tokens in {t_decode:.3f}s "
        f"({res['decode_tok_s']} tok/s)")
    return res


def main() -> int:
    """DEPRECATED shim: delegates to ``python -m repro serve``."""
    import warnings

    warnings.warn(
        "python -m repro.launch.serve is deprecated; use "
        "`python -m repro serve --config <run.yaml>` (this shim delegates "
        "through the same Run API)", DeprecationWarning, stacklevel=2)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    from ..configs import canonical
    from ..run import api as run_api

    doc = {
        "run": {
            "kind": "serve",
            "name": f"serve_{canonical(args.arch)}",
            "serve": {"batch": args.batch, "prompt_len": args.prompt_len,
                      "gen": args.gen, "ckpt": args.ckpt},
        },
        "arch": {"component_key": "arch_config",
                 "variant_key": canonical(args.arch),
                 "config": {"reduced": args.reduced}},
        "model": {"component_key": "model", "variant_key": "auto",
                  "config": {"arch_config": {"instance_key": "arch"}}},
    }
    run_api.execute_doc(doc, log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
