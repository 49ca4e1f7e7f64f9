"""Paged KV cache + radix prefix sharing (PR-6 tentpole).

The regression gate is the PR-5 determinism contract with one new clause:
at a fixed pool shape, a request's token stream is bitwise independent of
slot index, co-residents, admission order — and of whether its prefix was
served from the radix cache or prefilled cold.  Plus: allocator/refcount
correctness under slot churn and LRU eviction, chunked prefill never
stalling a mid-decode slot past one chunk, and the paged knobs through
the Run API.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import build_model
from repro.serve import (
    BlockAllocator,
    EngineError,
    OutOfBlocks,
    RadixPrefixIndex,
    Request,
    ServeEngine,
    shared_prefix_trace,
    synthetic_trace,
)


def _model(arch, **overrides):
    cfg = get_reduced(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# host-side bookkeeping units
# ---------------------------------------------------------------------------
def test_block_allocator_refcounts():
    a = BlockAllocator(4)
    b0 = a.alloc(2)
    assert a.n_free == 2 and a.n_used == 2
    a.retain(b0[0])                       # a sharer appears
    a.release(b0)                         # original holder retires
    assert a.n_free == 3                  # b0[1] freed, b0[0] still shared
    a.release(b0[0])
    assert a.n_free == 4
    a.check()
    with pytest.raises(OutOfBlocks):
        a.alloc(5)
    with pytest.raises(ValueError):
        a.release(b0[0])                  # double free


def test_radix_match_insert_evict():
    a = BlockAllocator(8)
    idx = RadixPrefixIndex(2, a)          # 2-token pages
    blocks = a.alloc(3)
    idx.insert([1, 2, 3, 4, 5, 6], blocks)
    assert idx.n_nodes == 3 and all(a.ref[b] == 2 for b in blocks)
    # full-block matching, capped by max_tokens
    assert [n.block for n in idx.match([1, 2, 3, 4, 9, 9])] == blocks[:2]
    assert [n.block for n in idx.match([1, 2, 3, 4, 5, 6], 4)] == blocks[:2]
    assert idx.match([7, 7, 7, 7]) == []
    # existing nodes win: a duplicate insert leaves the tree unchanged and
    # takes no reference on the caller's redundant block
    dup = a.alloc(1)
    idx.insert([1, 2], dup)
    assert idx.n_nodes == 3 and a.ref[dup[0]] == 1
    a.release(dup)
    # eviction only touches pages the tree alone holds, LRU-first,
    # cascading leaf -> parent
    a.release(blocks)                     # the "request" retires
    idx.match([1, 2])                     # touch the root page: now MRU
    evicted = idx.evict(a.n_free + 2)
    assert evicted == 2 and idx.n_nodes == 1
    assert [n.block for n in idx.match([1, 2])] == [blocks[0]]
    idx.evict(8)
    assert idx.n_nodes == 0 and a.n_free == 8
    a.check()


# ---------------------------------------------------------------------------
# the determinism contract, extended to prefix sharing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", [
    "qwen1p5_0p5b",                       # GQA
    "deepseek_v3_671b",                   # MLA latent pages + MoE layers
])
def test_paged_engine_shared_prefix_matches_solo(arch):
    """Mixed continuous-batching over a prefix-heavy trace == each request
    alone in a fresh engine.  The solo engine never has a warm radix cache,
    so this is simultaneously the slot/co-resident independence gate AND
    the cache-hit == cold-prefill bitwise gate."""
    model, params = _model(arch)
    max_len = 48
    trace = shared_prefix_trace(6, model.cfg.vocab, prefix_len=16,
                                n_prefixes=1, seed=7, rate=0.0,
                                prompt_lens=(4, 8), gen_tokens=(4, 6),
                                temperature=0.7, top_k=12, top_p=0.9,
                                max_len=max_len)
    trace[1].temperature = 0.0            # greedy and sampled mixed in-flight
    kw = dict(n_slots=2, max_len=max_len, block_len=8, prefill_chunk=8)
    engine = ServeEngine(model, params, **kw)
    res = engine.run(trace, realtime=False)
    assert res["completed"] == len(trace)
    assert res["prefill_cache_hit_rate"] > 0
    cached = [r["cached_tokens"] for r in res["requests"]]
    assert cached[0] == 0 and all(c == 16 for c in cached[1:])
    streams = {r["id"]: r["gen_ids"] for r in res["requests"]}

    solo = ServeEngine(model, params, **kw)
    for r in trace:
        alone = solo.run([r], realtime=False)["requests"][0]["gen_ids"]
        assert alone == streams[r.rid], (
            f"{arch} request {r.rid}: engine {streams[r.rid]} vs solo {alone}"
        )


def test_prefix_cache_off_is_bitwise_identical():
    """`prefix_cache: off` keeps the pool/programs and only disables the
    radix index — streams must not move."""
    model, params = _model("qwen1p5_0p5b")
    trace = shared_prefix_trace(5, model.cfg.vocab, prefix_len=16, seed=3,
                                prompt_lens=(4, 8), gen_tokens=(4,),
                                temperature=0.9, top_k=8, max_len=48)
    kw = dict(n_slots=2, max_len=48, block_len=8, prefill_chunk=16)
    on = ServeEngine(model, params, **kw).run(trace, realtime=False)
    off = ServeEngine(model, params, prefix_cache=False, **kw).run(
        trace, realtime=False)
    assert on["prefill_cache_hit_rate"] > 0
    assert off["prefill_cache_hit_rate"] == 0
    assert ([r["gen_ids"] for r in on["requests"]]
            == [r["gen_ids"] for r in off["requests"]])


def test_refcount_eviction_under_slot_churn():
    """A tight pool forces LRU eviction as retired prompts accumulate in
    the radix tree; the allocator invariants must survive the churn and
    every block must end up either free or tree-held."""
    model, params = _model("qwen1p5_0p5b")
    max_len = 32
    trace = synthetic_trace(8, model.cfg.vocab, seed=5, rate=0.0,
                            prompt_lens=(10, 14), gen_tokens=(4,),
                            max_len=max_len)
    engine = ServeEngine(model, params, n_slots=2, max_len=max_len,
                         block_len=8, prefill_chunk=8, n_blocks=6)
    res = engine.run(trace, realtime=False)
    assert res["completed"] == 8
    pg = res["paging"]
    assert pg["evictions"] > 0
    assert pg["free_blocks"] + pg["cached_blocks"] == pg["n_blocks"]
    engine._alloc.check()
    # every cached page is held exactly once (by its radix node)
    held = [n.block for n in engine._radix._nodes]
    assert len(set(held)) == len(held)
    assert all(engine._alloc.ref[b] == 1 for b in held)


def test_chunked_prefill_interleaves_decode():
    """A long cold admission is split into fixed chunks with a decode tick
    between them, so a mid-decode co-resident advances during the prefill
    — and its stream is still bitwise the solo stream."""
    model, params = _model("qwen1p5_0p5b")
    max_len = 48
    short = Request(rid=0, prompt=np.arange(3, 9, dtype=np.int32),
                    max_new=10, seed=1, temperature=0.8, top_k=16)
    long = Request(rid=1, prompt=np.asarray(
        np.random.default_rng(2).integers(3, model.cfg.vocab, 33),
        np.int32), max_new=4, seed=2, temperature=0.8, top_k=16)
    kw = dict(n_slots=2, max_len=max_len, block_len=8, prefill_chunk=8,
              prefix_cache=False)
    engine = ServeEngine(model, params, **kw)
    res = engine.run([short, long], realtime=False)
    # the 33-token prompt is 5 chunks; the short request was mid-decode, so
    # every chunk boundary but the last ran one tick
    assert res["interleaved_decode_ticks"] >= 4
    streams = {r["id"]: r["gen_ids"] for r in res["requests"]}
    solo = ServeEngine(model, params, **kw)
    for r in (short, long):
        alone = solo.run([r], realtime=False)["requests"][0]["gen_ids"]
        assert alone == streams[r.rid]


def test_paged_engine_sharded_single_device_matches_unsharded():
    """Paged serving under a 1-device mesh+plan (block axis data-sharded
    via plans.cache_shardings): streams match the unsharded paged engine."""
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.plans import make_plan

    model, params = _model("qwen1p5_0p5b")
    trace = shared_prefix_trace(4, model.cfg.vocab, prefix_len=16, seed=9,
                                prompt_lens=(4,), gen_tokens=(3,),
                                temperature=0.6, max_len=32)
    kw = dict(n_slots=2, max_len=32, block_len=8, prefill_chunk=8)
    plain = ServeEngine(model, params, **kw)
    want = [r["gen_ids"] for r in plain.run(trace, realtime=False)["requests"]]

    mesh = make_local_mesh(1, 1)
    sharded = ServeEngine(model, params, mesh=mesh, plan=make_plan("ddp"),
                          **kw)
    res = sharded.run(trace, realtime=False)
    assert [r["gen_ids"] for r in res["requests"]] == want
    assert res["prefill_cache_hit_rate"] > 0


def test_paged_pool_shards_heads_like_the_dense_cache():
    """A paged GQA leaf keeps its KV heads in one ``K * dh`` feature axis;
    the cache plan puts the model axis there, as on the dense cache's
    head axis, and the data axis on the block axis."""
    from repro.launch.mesh import make_local_mesh
    from repro.sharding.plans import cache_shardings, make_plan

    model, _ = _model("qwen1p5_0p5b")
    mesh = make_local_mesh(1, 1)
    for plan in ("ddp", "fsdp_tp"):
        paged = cache_shardings(make_plan(plan), mesh, jax.eval_shape(
            lambda: model.init_paged_cache(8, 4)), 8)
        dense = cache_shardings(make_plan(plan), mesh, jax.eval_shape(
            lambda: model.init_cache(2, 8)), 2)
        for leaf in ("k", "v"):
            assert tuple(paged["blocks"][leaf].spec) == tuple(
                dense["blocks"][leaf].spec)[:4]
            assert tuple(paged["blocks"][leaf].spec)[3] == "model"


# ---------------------------------------------------------------------------
# the paged layer loop: the pool crosses it in place
# ---------------------------------------------------------------------------
# Four layers, so each stack's layer loop runs more than once (the MLA toy
# then has one dense layer and a three-layer MoE stack).
_LOOP_ARCHS = ["qwen1p5_0p5b", "deepseek_v3_671b"]


def _engine_slots(n):
    return {"tokens": jnp.arange(3, 3 + n, dtype=jnp.int32),
            "pos": jnp.array([5, 9, 2][:n], jnp.int32),
            "active": jnp.array([True, True, False][:n]),
            "n_gen": jnp.zeros((n,), jnp.int32),
            "max_gen": jnp.full((n,), 8, jnp.int32),
            "eos": jnp.full((n,), -1, jnp.int32),
            "key": jnp.zeros((n, 2), jnp.uint32),
            "temperature": jnp.zeros((n,), jnp.float32),
            "top_k": jnp.zeros((n,), jnp.int32),
            "top_p": jnp.ones((n,), jnp.float32)}


def _paged_programs(model, params, n_blocks, bl=8, n_slots=3, max_pages=4,
                    chunk=8):
    """The engine's two paged programs compiled with a float32 pool donated
    (a bfloat16 scatter is widened on the CPU, which adds whole-pool
    converts); returns (pool bytes, pool leaf shapes, compiled programs)."""
    from repro.train import steps as ST

    pool = jax.eval_shape(
        lambda: model.init_paged_cache(n_blocks, bl, jnp.float32))
    leaves = jax.tree_util.tree_leaves(pool)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    tick = jax.jit(ST.make_engine_step(model, paged=True),
                   donate_argnums=(1, 2)).lower(
        params, pool, _engine_slots(n_slots),
        jax.ShapeDtypeStruct((n_slots, max_pages), jnp.int32)).compile()
    prefill = jax.jit(ST.make_prefill_chunk_step(model),
                      donate_argnums=(1,)).lower(
        params, pool, jax.ShapeDtypeStruct((max_pages,), jnp.int32),
        jax.ShapeDtypeStruct((chunk,), jnp.int32), i32, i32).compile()
    return (sum(a.size * a.dtype.itemsize for a in leaves),
            {tuple(a.shape) for a in leaves}, (tick, prefill))


@pytest.mark.parametrize("arch", _LOOP_ARCHS)
def test_paged_programs_update_the_pool_in_place(arch):
    """The decode tick and the prefill chunk carry the donated pool through
    the layer loop: their temporaries do not grow with the pool, and no
    copy of a pool leaf appears in either compiled program."""
    import re

    model, params = _model(arch, n_layers=4)
    small_b, _, small = _paged_programs(model, params, 64)
    big_b, shapes, big = _paged_programs(model, params, 512)
    copied = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")
    for name, s, b in zip(("tick", "chunk"), small, big):
        growth = (b.memory_analysis().temp_size_in_bytes
                  - s.memory_analysis().temp_size_in_bytes)
        assert growth < 0.1 * (big_b - small_b), (name, growth)
        copies = {tuple(int(d) for d in m.group(1).split(","))
                  for m in copied.finditer(b.as_text()) if m.group(1)}
        assert not copies & shapes, (name, copies & shapes)


def _per_layer_reference(model, params, pool, x, block):
    """A plain Python ``for`` over the layers: each layer's block runs, as a
    program of its own like one trip of the loop, on its own one-layer
    slice of the pool, and the slices are restacked."""
    new_pool = {}
    for name, kind, idxs in model._stacks():
        layer = jax.jit(functools.partial(block, kind))
        slices = []
        for i in range(len(idxs)):
            lp = jax.tree_util.tree_map(lambda a: a[i], params[name])
            lc = jax.tree_util.tree_map(lambda a: a[i:i + 1], pool[name])
            x, lc = layer(lp, lc, x)
            slices.append(lc)
        new_pool[name] = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *slices)
    return x, new_pool


@pytest.mark.parametrize("arch", _LOOP_ARCHS)
def test_paged_layer_loop_matches_per_layer_reference(arch):
    """The carried layer loop of the decode tick and the prefill chunk
    gives exactly the logits and pool of a per-layer loop over the same
    blocks, at float32 on the CPU, with live, dropped and unallocated
    (-1) page entries in play."""
    from repro.models import transformer as T

    model, params = _model(arch, n_layers=4)
    cfg = model.cfg
    n_blocks, bl = 12, 4
    rng = np.random.default_rng(0)
    pool = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), jnp.float32),
        model.init_paged_cache(n_blocks, bl, jnp.float32))
    pages = jnp.asarray([[3, 7, 1, -1], [0, 10, 11, 2], [5, 6, -1, -1]],
                        jnp.int32)
    tokens = jnp.asarray([4, 9, 17], jnp.int32)
    positions = jnp.asarray([5, 13, 2], jnp.int32)
    active = jnp.asarray([True, True, False])

    def ref_decode(params, pool):
        def block(kind, lp, lc, x):
            return T.decode_block_paged(cfg, kind, lp, lc, 0, x, positions,
                                        pages, active)

        x = jax.jit(model.embed_tokens)(params, tokens[:, None])
        x, new_pool = _per_layer_reference(model, params, pool, x, block)
        return jax.jit(model.logits)(params, x)[:, 0], new_pool

    def new_decode(params, pool):
        return model.decode_step(params, pool, tokens, positions,
                                 pages=pages, active=active)

    chunk = jnp.asarray(rng.integers(3, cfg.vocab, 8), jnp.int32)
    row, start, n_valid = pages[1], 4, 6

    def ref_chunk(params, pool):
        pos = start + jnp.arange(chunk.shape[0])

        def block(kind, lp, lc, x):
            return T.prefill_chunk_block(cfg, kind, lp, lc, 0, x, pos, row,
                                         n_valid)

        x = jax.jit(model.embed_tokens)(params, chunk[None])
        x, new_pool = _per_layer_reference(model, params, pool, x, block)
        last = jnp.take(x, n_valid - 1, axis=1)
        return jax.jit(model.logits)(params, last[:, None])[:, 0], new_pool

    def new_chunk(params, pool):
        return model.prefill_chunk(params, pool, row, chunk, start, n_valid)

    for ref, new in ((ref_decode, new_decode), (ref_chunk, new_chunk)):
        want = ref(params, pool)
        got = jax.jit(new)(params, pool)
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # the writes landed: not two untouched pools compared
        assert not all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                                       jax.tree_util.tree_leaves(pool)))


# ---------------------------------------------------------------------------
# configuration edges
# ---------------------------------------------------------------------------
def test_paged_rejected_for_windowed_and_ssm_archs():
    for arch, overrides in [("stablelm_1p6b", {"window": 8}),
                            ("zamba2_2p7b", {})]:
        model, params = _model(arch, **overrides)
        assert not model.supports_paged_cache()
        with pytest.raises(EngineError):
            ServeEngine(model, params, n_slots=2, max_len=16, block_len=8)
        # auto mode falls back to the dense slot pool and still serves
        engine = ServeEngine(model, params, n_slots=2, max_len=16)
        assert not engine.paged
        trace = synthetic_trace(2, model.cfg.vocab, seed=1, prompt_lens=(4,),
                                gen_tokens=(3,), max_len=16)
        assert engine.run(trace, realtime=False)["completed"] == 2


def test_paged_knob_validation():
    model, params = _model("qwen1p5_0p5b")
    with pytest.raises(EngineError):    # chunk off the block grid
        ServeEngine(model, params, n_slots=2, max_len=32, block_len=8,
                    prefill_chunk=12)
    with pytest.raises(EngineError):    # pool cannot hold one request
        ServeEngine(model, params, n_slots=2, max_len=32, block_len=8,
                    n_blocks=3)
    # a sole request larger than the free pool after full eviction is a
    # hard error, not a hang
    engine = ServeEngine(model, params, n_slots=2, max_len=32, block_len=8,
                         n_blocks=4, prefill_chunk=8)
    trace = synthetic_trace(3, model.cfg.vocab, seed=2, prompt_lens=(10,),
                            gen_tokens=(4,), max_len=32)
    assert engine.run(trace, realtime=False)["completed"] == 3


def test_serve_settings_paged_knobs():
    from repro.run.config import RunError, parse_run_doc

    doc = {
        "run": {"kind": "serve", "name": "p",
                "serve": {"engine": True, "n_slots": 2, "block_len": 8,
                          "n_blocks": 24, "prefill_chunk": 16,
                          "prefix_cache": False,
                          "workload": {"n_requests": 4, "prefix_len": 24,
                                       "n_prefixes": 2,
                                       "prompt_lens": [4, 8],
                                       "gen_tokens": 4}}},
        "arch": {"component_key": "arch_config", "variant_key": "qwen1p5_0p5b",
                 "config": {"reduced": True}},
    }
    s = parse_run_doc(doc).settings
    assert (s.block_len, s.n_blocks, s.prefill_chunk) == (8, 24, 16)
    assert not s.prefix_cache
    assert s.workload.prefix_len == 24 and s.workload.n_prefixes == 2
    with pytest.raises(RunError):
        parse_run_doc({"run": {"kind": "serve",
                               "serve": {"block_len": -2}}})
    with pytest.raises(RunError):
        parse_run_doc({"run": {"kind": "serve",
                               "serve": {"workload": {"prefix_len": -1}}}})


def test_execute_serve_paged_bench_fields(tmp_path, monkeypatch):
    """The Run API threads the paged knobs through and the tracked bench
    artifact carries the cache-hit-rate / hit-vs-cold TTFT rows."""
    from repro.run import api as run_api

    monkeypatch.chdir(tmp_path)
    doc = {
        "run": {"kind": "serve", "name": "pagedtest",
                "output_dir": str(tmp_path / "run"),
                "serve": {"engine": True, "n_slots": 2, "block_len": 8,
                          "prefill_chunk": 16, "compare_static": False,
                          "workload": {"n_requests": 4, "prefix_len": 16,
                                       "prompt_lens": [5], "gen_tokens": [3],
                                       "realtime": False}}},
        "arch": {"component_key": "arch_config", "variant_key": "qwen1p5_0p5b",
                 "config": {"reduced": True}},
        "model": {"component_key": "model", "variant_key": "auto",
                  "config": {"arch_config": {"instance_key": "arch"}}},
    }
    res = run_api.execute_doc(doc, log=lambda m: None)
    assert res["completed"] == 4
    assert res["prefill_cache_hit_rate"] > 0
    assert res["paging"]["block_len"] == 8
    import json

    b = json.loads((tmp_path / "BENCH_serve_pagedtest.json").read_text())
    for key in ("prefill_cache_hit_rate", "ttft_hit_s", "ttft_cold_s",
                "prefill_hit_s", "prefill_cold_s", "paging",
                "interleaved_decode_ticks"):
        assert key in b, key
    assert b["ttft_hit_s"] is not None and b["ttft_cold_s"] is not None
