"""Serving jobs: the continuous-batching ``ServeEngine`` over a paged KV
cache, on the model the registry builds, with the seed's weights.

Set-up builds the engine and runs its own warm-up, which compiles every
program the scheduler calls (prefill chunk, first token, slot write,
decode tick) against a sacrificial pool. The window is ``engine.run`` over
the traffic of this seed: requests arrive on their schedule (online) or
all at t = 0 (batch), and the run ends when the last one completes. With
``--trace 1`` the profiler runs for ``trace_seconds`` once every request
has had its first token.

Correctness: once the run is over and the engine is freed, a sample of
the finished requests drawn from the seed, the longest among them, is run
through the plain reference over each prompt and its served tokens; the
number compared is the widest gap by which a served token's reference
logit lies below the reference's best (the traffic is greedy).
"""
from __future__ import annotations

import time

import numpy as np


def build(ctx):
    from repro.config.registry import DEFAULT_REGISTRY as REG
    import repro.core.components  # noqa: F401  (populates the registry)
    from repro.serve.engine import ServeEngine

    spec = ctx.spec
    model = REG.build("model", "auto",
                      arch_config=ctx.prog.arch_config(ctx.config))
    engine = ServeEngine(model, ctx.to_program(ctx.weights()),
                         n_slots=spec["n_slots"], max_len=spec["max_len"],
                         greedy=True, block_len=spec["block_len"],
                         prefill_chunk=spec["prefill_chunk"],
                         prefix_cache=spec["prefix_cache"])
    for hook in ctx.hooks:
        hook(ctx, engine)
    return engine


def requests_of(ctx):
    from repro.serve.workload import Request

    from bench.traffic import generate

    reqs = generate.requests(ctx.traffic, ctx.seconds, ctx.seed,
                             ctx.config["vocab_size"])
    return [Request(rid=r["rid"], prompt=r["prompt"], max_new=r["max_new"],
                    arrival_s=r["arrival_s"], seed=r["rid"]) for r in reqs]


def _annotate(ctx, engine, n_requests):
    """Host spans around the engine's device calls. The profiler window
    opens at the first decode tick after every request has had its first
    token, so that no request's queueing or prefill waits on the profiler,
    and closes ``trace_seconds`` later."""
    state = {"on": False, "done": False, "firsts": 0, "opened": 0.0}

    def wrap(name, fn, gate):
        def call(*a, **k):
            if gate and not state["done"]:
                if not state["on"] and state["firsts"] >= n_requests:
                    ctx.trace_start()
                    state["on"], state["opened"] = True, time.perf_counter()
                elif (state["on"] and time.perf_counter() - state["opened"]
                      >= ctx.spec["trace_seconds"]):
                    ctx.trace_stop()
                    state["on"], state["done"] = False, True
            with ctx.span(name):
                return fn(*a, **k)
        return call

    first = engine._first

    def counted_first(*a, **k):
        state["firsts"] += 1
        return first(*a, **k)

    engine._tick = wrap("tick", engine._tick, True)
    engine._chunk = wrap("prefill_chunk", engine._chunk, False)
    engine._first = wrap("first_token", counted_first, False)
    engine._set_slot = wrap("slot_write", engine._set_slot, False)
    return state


def window(ctx, engine, requests, realtime: bool):
    traced = _annotate(ctx, engine, len(requests)) if ctx.trace else None
    ctx.window_begins()
    res = engine.run(requests, realtime=realtime, warmup=False)
    if traced is not None and traced["on"]:
        ctx.trace_stop()
    ctx.window_ends()
    return res


def log_program_memory(ctx, engine, tick, chunk):
    """The compiler's memory analysis of the decode tick and the prefill
    chunk (the engine's jitted programs, taken before any wrapping) at the
    run's shapes, read from the compilation cache."""
    import jax
    import jax.numpy as jnp

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    cache, slots = jax.eval_shape(engine._init_pool)
    pages = shape(jnp.asarray(engine._pt))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if hasattr(tick, "lower"):
        ctx.log_memory("decode_tick", tick.lower(engine.params, cache, slots,
                                                 pages))
    if hasattr(chunk, "lower"):
        ctx.log_memory("prefill_chunk", chunk.lower(
            engine.params, cache, shape(jnp.asarray(engine._pt[0])),
            jax.ShapeDtypeStruct((engine.prefill_chunk,), jnp.int32), i32,
            i32))


def record(ctx, res, requests):
    """What the metric readers read. The engine is synchronous (each tick
    waits for its tokens), so the seconds the profiler's start and stop
    held the host inside a tick are taken out of ``decode_s`` and
    ``elapsed_s``; they are 0 in an untraced run."""
    from bench import flops

    rows = res["requests"]
    done = [r for r in rows if r.get("finish") in ("eos", "length")]
    toggle = ctx.record["trace_toggle_s"]
    ctx.attempted = len(requests)
    ctx.failed = len(requests) - len(done)
    ctx.record.update({
        "rows": rows,
        "elapsed_s": res["elapsed_s"] - toggle,
        "decode_s": res["decode_s"] - toggle,
        "ticks": res["ticks"],
        "tick_ends_s": [t["t_s"] for t in res["timeline"]],
        "prompt_tokens": sum(r["prompt_len"] for r in rows),
        "cached_tokens": sum(r.get("cached_tokens", 0) for r in rows),
        "output_tokens": sum(r["n_gen"] for r in done),
        "flops": sum(flops.serve_request(ctx.ref, ctx.config, r["prompt_len"],
                                         r.get("cached_tokens", 0),
                                         r.get("n_gen", 0)) for r in rows),
    })
    between = np.diff(ctx.record["tick_ends_s"])
    if len(between):
        q = np.percentile(between, [10, 50, 90, 99]) * 1e3
        ctx.log_detail(
            f"tick to tick ms: p10 {q[0]:.2f}, p50 {q[1]:.2f}, p90 "
            f"{q[2]:.2f}, p99 {q[3]:.2f}, max {between.max() * 1e3:.2f}; host "
            f"outside ticks and prefill "
            f"{res['elapsed_s'] - res['decode_s'] - res['prefill_s']:.3f} s; "
            f"profiler start and stop {toggle:.3f} s")


def sample(ctx, rows, prompts):
    """Finished requests to compare: the one with the most served tokens,
    then others drawn from the seed until ``check_tokens`` are covered."""
    from bench.traffic import generate

    done = [r for r in rows if r.get("n_gen", 0) > 0]
    longest = max(done, key=lambda r: (r["n_gen"], r["prompt_len"]))
    order = generate.rng(ctx.seed, "check_sample").permutation(len(done))
    picked, n = [longest], longest["n_gen"]
    for i in order:
        if n >= ctx.spec["check_tokens"]:
            break
        if done[i] is not longest:
            picked.append(done[i])
            n += done[i]["n_gen"]
    return [(np.asarray(prompts[r["id"]], np.int32),
             np.asarray(r["gen_ids"], np.int32)) for r in picked]


def reference_logits(ctx, modes=("f32",)):
    """A jitted function (weights, tokens [1, max_len], rows [K]) -> the
    reference logits of those rows in each of ``modes``."""
    import jax

    ref, c = ctx.ref, ctx.config

    @jax.jit
    def fn(w, tokens, rows):
        out = []
        for mode in modes:
            h = ref.hidden(w, tokens, c, mode)[0]
            out.append(ref.head(w, h[rows][None], c, mode)[0])
        return out

    return fn


def gaps(ctx, picked, modes=("f32",), altered=False):
    """Per sampled request: the reference's gap at every served token, and
    for each further mode, the gap of the token that mode puts first;
    ``altered`` adds the gaps of every served token with its id moved by
    one (a fault planted where tokens are produced)."""
    import jax.numpy as jnp

    from bench import checks

    L, K = ctx.spec["max_len"], ctx.spec["max_output"]
    fn = reference_logits(ctx, modes)
    w = ctx.weights()
    served, others = [], {m: [] for m in modes[1:]}
    if altered:
        others["altered"] = []
    for prompt, gen in picked:
        seq = np.concatenate([prompt, gen[:-1]])
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(seq)] = seq
        rows = np.zeros((K,), np.int32)
        n = len(gen)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        outs = [np.asarray(o, np.float64)[:n]
                for o in fn(w, jnp.asarray(toks), jnp.asarray(rows))]
        served.append(checks.served_gaps(outs[0], gen))
        for m, o in zip(modes[1:], outs[1:]):
            others[m].append(checks.served_gaps(outs[0], o.argmax(-1)))
        if altered:
            others["altered"].append(checks.served_gaps(
                outs[0], (gen + 1) % outs[0].shape[-1]))
    return served, others


def run(ctx, realtime: bool):
    engine = build(ctx)
    programs = engine._tick, engine._chunk
    requests = requests_of(ctx)
    engine._warmup([r.prompt_len for r in requests])
    res = window(ctx, engine, requests, realtime)
    log_program_memory(ctx, engine, *programs)
    del engine
    record(ctx, res, requests)
    ctx.log_detail(f"engine: {res['ticks']} ticks, decode {res['decode_s']} "
                   f"s, prefill {res['prefill_s']} s, run {res['elapsed_s']}"
                   f" s")
    prompts = {r.rid: r.prompt for r in requests}
    served, _ = gaps(ctx, sample(ctx, res["requests"], prompts))
    worst = max(float(g.max()) for g in served)
    ctx.check("logit_gap", worst, ctx.spec["limits"]["logit_gap"])
    ctx.log_detail(f"logit_gap over {sum(len(g) for g in served)} served "
                   f"tokens of {len(served)} requests")
