"""Serving engine + the ckpt-to-serve / eval / accounting regression fixes.

The load-bearing invariant: continuous-batching output per request is
IDENTICAL to running that request alone in a single slot — across the GQA
ring-buffer, MLA, and hybrid SSD cache families, under mixed sampling, with
mid-flight admission churn.  Plus: the static-batch shim reproduces the
legacy host-looped greedy benchmark token-for-token, serving restores
params from real training checkpoints (both formats), the perplexity
evaluator weights ragged batches correctly without re-jitting, and the
benchmark accounting is consistent.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import build_model
from repro.serve import (
    Request,
    ServeEngine,
    load_params,
    sample_tokens,
    static_trace,
    synthetic_trace,
)
from repro.train import steps as ST


def _model(arch, **overrides):
    cfg = get_reduced(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# engine invariants: continuous batching == solo, across cache families
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,overrides", [
    ("qwen1p5_0p5b", {}),                 # GQA, full cache
    ("stablelm_1p6b", {"window": 8}),     # GQA ring-buffer cache
    ("deepseek_v3_671b", {}),             # MLA latent cache
    ("zamba2_2p7b", {}),                  # hybrid SSD + shared-attn cache
])
def test_engine_matches_solo(arch, overrides):
    """Every request's token stream from the mixed continuous-batching run
    equals generating it alone in a single slot of an identically-shaped
    pool (same seed/sampling) — output never depends on co-resident
    requests, admission order, or slot index.

    The solo pool is the same width deliberately: XLA may fuse the tick
    differently per batch shape (1-ulp bf16 reassociation differences that
    can flip a sampling near-tie), so the determinism contract is stated at
    a fixed pool shape."""
    model, params = _model(arch, **overrides)
    max_len = 32
    trace = synthetic_trace(5, model.cfg.vocab, seed=11, rate=0.0,
                            prompt_lens=(6, 10), gen_tokens=(3, 6),
                            temperature=0.8, top_k=16, top_p=0.95,
                            max_len=max_len)
    trace[0].temperature = 0.0            # greedy and sampled mixed in-flight
    engine = ServeEngine(model, params, n_slots=2, max_len=max_len)
    res = engine.run(trace, realtime=False)
    assert res["completed"] == len(trace)
    streams = {r["id"]: r["gen_ids"] for r in res["requests"]}

    solo = ServeEngine(model, params, n_slots=2, max_len=max_len)
    for r in trace:
        alone = solo.run([r], realtime=False)["requests"][0]["gen_ids"]
        assert alone == streams[r.rid], (
            f"{arch} request {r.rid}: engine {streams[r.rid]} vs solo {alone}"
        )


def test_engine_slot_reuse_is_clean():
    """A slot freed by a short request serves the next queued request with
    no state leakage (more requests than slots forces reuse)."""
    model, params = _model("qwen1p5_0p5b")
    trace = synthetic_trace(7, model.cfg.vocab, seed=2, prompt_lens=(5,),
                            gen_tokens=(2, 5), temperature=0.5, max_len=16)
    engine = ServeEngine(model, params, n_slots=2, max_len=16)
    res = engine.run(trace, realtime=False)
    assert res["completed"] == 7
    assert res["slot_utilization"] > 0
    solo = ServeEngine(model, params, n_slots=2, max_len=16)
    last = trace[-1]
    assert solo.run([last], realtime=False)["requests"][0]["gen_ids"] == \
        res["requests"][last.rid]["gen_ids"]


def test_engine_eos_retires_slot():
    """A request retires the moment it samples its EOS token."""
    model, params = _model("qwen1p5_0p5b")
    prompt = np.arange(3, 9, dtype=np.int32)
    probe = Request(rid=0, prompt=prompt, max_new=6, seed=4)
    engine = ServeEngine(model, params, n_slots=1, max_len=16)
    ids = engine.run([probe], realtime=False)["requests"][0]["gen_ids"]
    eos = ids[2]                      # make the 3rd greedy token the EOS
    req = Request(rid=0, prompt=prompt, max_new=6, seed=4, eos_id=int(eos))
    row = engine.run([req], realtime=False)["requests"][0]
    assert row["gen_ids"] == ids[:3]
    assert row["finish"] == "eos"
    assert row["n_gen"] == 3


def test_engine_metrics_shape():
    model, params = _model("qwen1p5_0p5b")
    trace = synthetic_trace(4, model.cfg.vocab, seed=0, rate=50.0,
                            prompt_lens=(6,), gen_tokens=(4,), max_len=16)
    res = ServeEngine(model, params, n_slots=2, max_len=16).run(trace)
    assert res["completed"] == res["n_requests"] == 4
    assert res["generated_tokens"] == 16
    assert res["decode_tokens"] == 12          # firsts belong to prefill
    assert set(res["ttft_s"]) == {"p50", "p95", "p99"}
    assert set(res["tick_ms"]) == {"p50", "p95", "p99"}
    assert 0 < res["slot_utilization"] <= 1
    for row in res["requests"]:
        assert row["n_gen"] == len(row["gen_ids"]) == 4
        assert row["ttft_s"] >= 0


def test_engine_sharded_single_device_matches_unsharded():
    """Sharded serving wiring: params laid out under the plan, cache slot
    axis data-sharded (plans.cache_shardings) — on a 1-device mesh the
    token streams must match the unsharded engine exactly."""
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.plans import make_plan

    model, params = _model("qwen1p5_0p5b")
    trace = synthetic_trace(3, model.cfg.vocab, seed=6, prompt_lens=(5, 7),
                            gen_tokens=(3,), temperature=0.6, max_len=16)
    plain = ServeEngine(model, params, n_slots=2, max_len=16)
    want = [r["gen_ids"] for r in plain.run(trace, realtime=False)["requests"]]

    mesh = make_local_mesh(1, 1)
    sharded = ServeEngine(model, params, n_slots=2, max_len=16,
                          mesh=mesh, plan=make_plan("ddp"))
    got = [r["gen_ids"] for r in sharded.run(trace, realtime=False)["requests"]]
    assert got == want


def test_workload_trace_is_seeded():
    a = synthetic_trace(6, 512, seed=9, rate=4.0)
    b = synthetic_trace(6, 512, seed=9, rate=4.0)
    c = synthetic_trace(6, 512, seed=10, rate=4.0)
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.arrival_s, ra.max_new, ra.seed) == \
            (rb.arrival_s, rb.max_new, rb.seed)
    assert any(not np.array_equal(ra.prompt, rc.prompt)
               for ra, rc in zip(a, c))
    assert all(x.arrival_s <= y.arrival_s for x, y in zip(a, a[1:]))


# ---------------------------------------------------------------------------
# sampling head
# ---------------------------------------------------------------------------
def test_sampling_head():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 64)) * 3.0
    keys = jax.vmap(jax.random.fold_in)(
        jnp.broadcast_to(jax.random.PRNGKey(7), (4, 2)), jnp.arange(4))
    zeros, ones = jnp.zeros((4,)), jnp.ones((4,))
    # temperature <= 0 => exact argmax (legacy greedy)
    greedy = sample_tokens(logits, keys, zeros, jnp.zeros((4,), jnp.int32),
                           ones)
    assert np.array_equal(np.asarray(greedy),
                          np.asarray(jnp.argmax(logits, -1)))
    # top_k=1 forces the argmax even at high temperature
    k1 = sample_tokens(logits, keys, ones * 5.0, jnp.ones((4,), jnp.int32),
                       ones)
    assert np.array_equal(np.asarray(k1), np.asarray(jnp.argmax(logits, -1)))
    # same keys -> same draw; different keys -> (almost surely) different
    s1 = sample_tokens(logits, keys, ones, jnp.zeros((4,), jnp.int32), ones)
    s2 = sample_tokens(logits, keys, ones, jnp.zeros((4,), jnp.int32), ones)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    # top_k restricts the support
    for _ in range(8):
        keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
        sk = sample_tokens(logits, keys, ones * 2.0,
                           jnp.full((4,), 4, jnp.int32), ones)
        top4 = np.argsort(np.asarray(logits), axis=-1)[:, -4:]
        for row in range(4):
            assert int(sk[row]) in top4[row]
    # tiny top_p collapses to the mode
    sp = sample_tokens(logits, keys, ones, jnp.zeros((4,), jnp.int32),
                       ones * 1e-6)
    assert np.array_equal(np.asarray(sp), np.asarray(jnp.argmax(logits, -1)))


# ---------------------------------------------------------------------------
# static-batch shim: engine-routed, numerics-identical to the legacy loop
# ---------------------------------------------------------------------------
def test_static_shim_matches_legacy_loop():
    from repro.launch.serve import serve_benchmark

    model, params = _model("qwen1p5_0p5b")
    cfg = model.cfg
    B, P, G, seed = 3, 12, 5, 0
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, P), 3,
                                 cfg.vocab)
    # the pre-engine implementation: batched prefill + host-looped argmax
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, max_len=P + G))(
        params, {"tokens": prompts})
    step = jax.jit(ST.make_serve_step(model), donate_argnums=(1,))
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    legacy = [tokens]
    for i in range(G - 1):
        tokens, _, cache = step(params, cache, tokens,
                                jnp.full((B,), P + i, jnp.int32))
        legacy.append(tokens)
    ref = np.stack(jax.device_get(legacy), axis=1)

    res = serve_benchmark(model, batch=B, prompt_len=P, gen=G, seed=seed,
                          params=params, log=lambda m: None)
    assert np.array_equal(ref, np.array(res["generated_ids"]))


def test_benchmark_accounting_consistent():
    """All rows come back; prefill-sampled firsts are excluded from decode
    throughput but included in the generation totals."""
    from repro.launch.serve import serve_benchmark

    model, params = _model("qwen1p5_0p5b")
    B, G = 3, 4
    res = serve_benchmark(model, batch=B, prompt_len=8, gen=G, seed=1,
                          params=params, log=lambda m: None)
    assert len(res["generated_ids"]) == B
    assert all(len(row) == G for row in res["generated_ids"])
    assert res["generated_ids_0"] == res["generated_ids"][0]
    assert res["decode_steps"] == G - 1
    assert res["decode_tokens"] == B * (G - 1)
    assert res["gen_tokens_total"] == B * G


# ---------------------------------------------------------------------------
# ckpt-to-serve: params-only restore from full TrainState checkpoints
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def trained_ckpts(tmp_path_factory):
    """A real TrainState saved in BOTH formats: the PR-4 sharded dir (as a
    SIGKILL-style committed step) and the legacy single-npz."""
    from repro.ckpt import AsyncCheckpointer
    from repro.optim.adamw import AdamW
    from repro.train.checkpoint import save_checkpoint

    model, _ = _model("qwen1p5_0p5b")
    opt = AdamW(lr=1e-3)
    state = ST.init_train_state(model, opt, jax.random.PRNGKey(3))
    base = tmp_path_factory.mktemp("serve_ckpts")
    ck = AsyncCheckpointer(os.path.join(base, "dir"))
    ck.save(state, 5)
    ck.close()
    save_checkpoint(state, os.path.join(base, "npz"), 5)
    return model, state, {
        "dir": os.path.join(base, "dir"),
        "npz": os.path.join(base, "npz", "step_00000005.npz"),
    }


@pytest.mark.parametrize("fmt", ["dir", "npz"])
def test_serve_restores_training_checkpoint(trained_ckpts, fmt):
    """The old bug: restore_checkpoint(params, ckpt) crashed on the
    {params, opt, step} structure.  load_params restores the params subtree
    from either format, into an eval_shape target (no double init)."""
    model, state, paths = trained_ckpts
    restored = load_params(model, ckpt=paths[fmt])
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fmt", ["dir", "npz"])
def test_serve_benchmark_with_ckpt(trained_ckpts, fmt):
    """End to end: `serve --ckpt <training checkpoint>` runs and its greedy
    stream matches serving the restored params directly."""
    from repro.launch.serve import serve_benchmark

    model, state, paths = trained_ckpts
    got = serve_benchmark(model, batch=2, prompt_len=6, gen=3, seed=0,
                          ckpt=paths[fmt], log=lambda m: None)
    want = serve_benchmark(model, batch=2, prompt_len=6, gen=3, seed=0,
                           params=state["params"], log=lambda m: None)
    assert got["generated_ids"] == want["generated_ids"]


def test_restore_params_bare_params_npz(tmp_path):
    """Backcompat: a params-only npz (no params/ prefix) still restores."""
    from repro.ckpt import format as CF
    from repro.train.checkpoint import restore_params

    model, params = _model("qwen1p5_0p5b")
    arrays = {k: np.asarray(v) for k, v in CF.flatten_with_paths(params)}
    path = tmp_path / "bare.npz"
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    restored = restore_params(like, str(path))
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# evaluator: sample-weighted mean + hoisted jit
# ---------------------------------------------------------------------------
class _ToyDataset:
    """10 fixed (x, y) samples of seq_len 8."""

    def __init__(self, vocab, n=10, seq=8):
        rng = np.random.default_rng(0)
        self.xs = rng.integers(0, vocab, size=(n, seq)).astype(np.int32)
        self.ys = rng.integers(0, vocab, size=(n, seq)).astype(np.int32)

    def __len__(self):
        return len(self.xs)

    def sample(self, i):
        return self.xs[i], self.ys[i]


def test_evaluator_ragged_batch_weighting():
    """n_samples=10, batch=4 -> batches of 4/4/2: the mean must weight by
    sample count (== the mean over all 10 per-sample losses), not average
    the three batch means."""
    from repro.core.evaluator import PerplexityEvaluator

    model, params = _model("qwen1p5_0p5b")
    ds = _ToyDataset(model.cfg.vocab)
    ev = PerplexityEvaluator(ds, n_samples=10, offset=0, batch=4)
    got = ev(model, params)

    per_sample = []
    for i in range(10):
        x, y = ds.sample(i)
        batch = {"tokens": jnp.asarray(x[None]), "labels": jnp.asarray(y[None])}
        per_sample.append(float(ST.compute_loss(model, params, batch)[0]))
    want = float(np.mean(per_sample))
    assert got["loss"] == pytest.approx(want, rel=1e-4)
    assert got["ppl"] == pytest.approx(float(np.exp(want)), rel=1e-3)

    # the old unweighted mean-of-batch-means over-weights the ragged tail
    # batch; on this (fixed, seeded) data the two values measurably differ
    b1 = float(np.mean(per_sample[0:4]))
    b2 = float(np.mean(per_sample[4:8]))
    b3 = float(np.mean(per_sample[8:10]))
    buggy = float(np.mean([b1, b2, b3]))
    assert abs(got["loss"] - buggy) > abs(got["loss"] - want)


def test_evaluator_jit_is_hoisted():
    """Repeated eval windows reuse ONE jitted loss per model — no fresh
    jax.jit wrapper (and recompile) per __call__."""
    from repro.core.evaluator import PerplexityEvaluator

    model, params = _model("qwen1p5_0p5b")
    ds = _ToyDataset(model.cfg.vocab, n=4, seq=8)
    ev = PerplexityEvaluator(ds, n_samples=4, offset=0, batch=4)
    fn_first = ev._loss_fn(model)
    r1 = ev(model, params)
    assert ev._loss_fn(model) is fn_first
    r2 = ev(model, params)
    assert r1 == r2


# ---------------------------------------------------------------------------
# engine settings through the Run API
# ---------------------------------------------------------------------------
def test_serve_settings_blocks():
    from repro.run.config import RunError, parse_run_doc

    doc = {
        "run": {"kind": "serve", "name": "e",
                "serve": {"engine": True, "n_slots": 2, "max_len": 24,
                          "sampling": {"temperature": 0.7, "top_k": 8},
                          "workload": {"n_requests": 3,
                                       "prompt_lens": [4, 6],
                                       "gen_tokens": 4}}},
        "arch": {"component_key": "arch_config", "variant_key": "qwen1p5_0p5b",
                 "config": {"reduced": True}},
    }
    cfg = parse_run_doc(doc)
    s = cfg.settings
    assert s.engine and s.n_slots == 2
    assert s.sampling.temperature == 0.7 and s.sampling.top_k == 8
    assert s.workload.prompt_lens == [4, 6]
    assert s.workload.gen_tokens == [4]      # bare int coerces to a list
    with pytest.raises(RunError):
        parse_run_doc({"run": {"kind": "serve",
                               "serve": {"sampling": {"top_p": 0.0}}}})
    with pytest.raises(RunError):
        parse_run_doc({"run": {"kind": "serve",
                               "serve": {"workload": {"nope": 1}}}})


def test_execute_serve_engine_writes_bench(tmp_path, monkeypatch):
    from repro.run import api as run_api

    monkeypatch.chdir(tmp_path)
    doc = {
        "run": {"kind": "serve", "name": "enginetest",
                "output_dir": str(tmp_path / "run"),
                "serve": {"engine": True, "n_slots": 2, "max_len": 16,
                          "compare_static": False,
                          "workload": {"n_requests": 3, "prompt_lens": [5],
                                       "gen_tokens": [3], "realtime": False}}},
        "arch": {"component_key": "arch_config", "variant_key": "qwen1p5_0p5b",
                 "config": {"reduced": True}},
        "model": {"component_key": "model", "variant_key": "auto",
                  "config": {"arch_config": {"instance_key": "arch"}}},
    }
    res = run_api.execute_doc(doc, log=lambda m: None)
    assert res["completed"] == 3
    assert res["generated_tokens"] == 9
    bench = tmp_path / "BENCH_serve_enginetest.json"
    assert bench.exists()
    import json

    b = json.loads(bench.read_text())
    assert b["n_requests"] == 3 and "requests" not in b
    assert (tmp_path / "run" / "result.json").exists()
