"""Continuous-batching serving engine (the fifth pillar).

Two cache layouts share one scheduler:

- **Paged** (the default when the arch supports it): the KV cache is a
  block pool (``model.init_paged_cache(n_blocks, block_len)``, every leaf
  ``[L, n_blocks, block_len, ...]``) and each slot owns a page-table row of
  physical block ids.  A radix prefix index (:mod:`repro.serve.paging`)
  maps shared prompt prefixes onto refcounted pages, so a request whose
  prompt extends a cached stream only prefills its tail — and admission is
  *chunked*: fixed-shape prompt chunks interleave with decode ticks, so a
  long prefill can never stall in-flight decodes for more than one chunk.
- **Dense** slot rows (``model.init_cache(n_slots, max_len)``) for archs a
  block pool cannot express — sliding-window ring buffers, SSM state,
  hybrids — and for ``block_len=0`` (the static shim pins this for bitwise
  compatibility with the pre-paging engine).

Every tick decodes all slots in one fused jitted step
(``train.steps.make_engine_step``: decode + on-device sampling head + stop
flags, cache and slot state donated); slots retire on EOS or budget,
immediately releasing their pages (prefix pages stay cached in the radix
tree until LRU eviction needs the space).

Determinism contract: at a fixed pool shape, a request's token stream
depends only on its own prompt, sampling settings, and seed — never on
slot index, admission order, co-resident requests, or (paged) whether its
prefix came from the radix cache or a cold prefill.  The cache-hit half
holds because pages are written by a fixed-shape chunk program whose
values cannot depend on prompt length or chunk grouping, and only
chunk-written prompt pages are ever shared.  ``docs/serving.md`` spells
out the full argument; ``tests/test_serve_paging.py`` enforces it.

Sharded serving reuses :mod:`repro.sharding.plans`: params laid out under
the plan, the cache's slot/block axis data-sharded (``cache_shardings``).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding import plans as PL
from ..telemetry.spans import SAMPLE, scope, span
from ..train import steps as ST
from .paging import BlockAllocator, RadixPrefixIndex
from .sampling import request_key, sample_tokens
from .workload import Request, percentiles

DEFAULT_BLOCK_LEN = 16


class EngineError(Exception):
    """Engine misconfiguration (unservable arch, request does not fit)."""


def load_params(model, ckpt: str = "", seed: int = 0):
    """Params for serving: restore a TRAINING checkpoint (full
    ``{params, opt, step}`` TrainState, either the sharded-dir or legacy npz
    format) params-only — or random-init when no checkpoint is given.

    With a checkpoint the target structure comes from ``jax.eval_shape``
    (no throwaway full ``model.init`` allocation before the restore).
    """
    if not ckpt:
        return model.init(jax.random.PRNGKey(seed))
    from ..train.checkpoint import restore_params

    like = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    return restore_params(like, ckpt)


class ServeEngine:
    """Continuous-batching engine over one resolved model."""

    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 cache_dtype=jnp.bfloat16, mesh=None, plan=None,
                 greedy: bool = False, block_len: Optional[int] = None,
                 n_blocks: int = 0, prefill_chunk: int = 0,
                 prefix_cache: bool = True,
                 deadline_s: float = 0.0, watchdog_s: float = 0.0,
                 fault_injector=None, telemetry=None,
                 log: Optional[Callable[[str], None]] = None):
        """``greedy=True`` compiles a sampler-free decode tick — use it when
        EVERY request this engine will serve is greedy (the static shim, or
        an all-greedy workload); the engine rejects sampled requests then.
        The variant is fixed per engine because greedy and general ticks
        are different fused programs (see ``make_engine_step``).

        ``block_len=None`` (default) auto-selects: paged KV cache with
        ``DEFAULT_BLOCK_LEN``-token pages when the arch supports it, the
        dense slot pool otherwise.  ``block_len=0`` forces dense;
        ``block_len>0`` forces paged (raising for unsupported archs).
        ``n_blocks=0`` sizes the pool to ``(n_slots + 1) * max_pages`` —
        full residency plus one request's worth of retained prefix pages.
        ``prefill_chunk`` (default ``2 * block_len``) is the fixed chunk
        the admission prefill is split into — the TTFT budget a prefill
        may stall co-resident decodes, and the grid cached pages are
        canonical on (must be a multiple of ``block_len``).
        ``prefix_cache=False`` keeps the block pool but disables radix
        matching/insertion (every admission prefills cold).
        """
        cfg = model.cfg
        if cfg.arch_type == "audio" or cfg.n_patches:
            raise EngineError(
                f"{cfg.name}: the serving engine drives text decoders; "
                f"audio/vlm prompts need modality extras the slot scheduler "
                f"does not carry")
        if n_slots < 1 or max_len < 2:
            raise EngineError(f"need n_slots >= 1 and max_len >= 2, got "
                              f"{n_slots}/{max_len}")
        if deadline_s < 0 or watchdog_s < 0:
            raise EngineError(f"deadline_s/watchdog_s must be >= 0, got "
                              f"{deadline_s}/{watchdog_s}")
        self.model = model
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.cache_dtype = cache_dtype
        # resilience: per-request wall deadline (0 = none; Request.deadline_s
        # overrides per request), a no-progress watchdog on the fused tick
        # (0 = off; only sane with warmup, else compile time trips it), and
        # a fault injector for deterministic serve_stall chaos
        self.deadline_s = float(deadline_s)
        self.watchdog_s = float(watchdog_s)
        self.fault_injector = fault_injector
        # optional TelemetryRecorder: per-request lifecycle spans
        # (queued -> prefill -> decode) and a headline metric row per run
        self.telemetry = telemetry
        self.log = log or (lambda msg: None)
        self.mesh, self.plan = mesh, plan
        supports_paged = model.supports_paged_cache()
        if block_len is None:
            self.block_len = DEFAULT_BLOCK_LEN if supports_paged else 0
        else:
            self.block_len = int(block_len)
            if self.block_len > 0 and not supports_paged:
                raise EngineError(
                    f"{cfg.name}: paged KV cache needs full-context "
                    f"attention decode layers (arch {cfg.arch_type}, window "
                    f"{cfg.window}); set block_len: 0 for the dense pool")
        self.paged = self.block_len > 0
        if self.paged:
            self.block_len = min(self.block_len, self.max_len)
            self.max_pages = -(-self.max_len // self.block_len)
            self.n_blocks = int(n_blocks) or (self.n_slots + 1) * self.max_pages
            if self.n_blocks < self.max_pages:
                raise EngineError(
                    f"n_blocks {self.n_blocks} cannot hold one max_len "
                    f"request ({self.max_pages} pages of {self.block_len})")
            chunk = int(prefill_chunk) or 2 * self.block_len
            if chunk < 1 or chunk % self.block_len:
                raise EngineError(
                    f"prefill_chunk {chunk} must be a positive multiple of "
                    f"block_len {self.block_len}: the chunk grid is what "
                    f"makes cached pages bitwise canonical")
            self.prefill_chunk = min(chunk, self.max_pages * self.block_len)
            self.prefix_cache = bool(prefix_cache)
        else:
            self.max_pages = 0
            self.n_blocks = 0
            self.prefill_chunk = 0
            self.prefix_cache = False
        if mesh is not None and plan is not None:
            self.mesh_ctx = PL.mesh_context(plan, mesh)
            pshapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            psh, self.shard_warnings = PL.param_shardings(
                plan, mesh, pshapes, model.param_axes())
            self.params = jax.device_put(params, psh)
        else:
            self.mesh_ctx = None
            self.shard_warnings = []
            self.params = params
        self.greedy = bool(greedy)
        self._tick = jax.jit(
            ST.make_engine_step(model, self.mesh_ctx, greedy=self.greedy,
                                paged=self.paged),
            donate_argnums=(1, 2))
        self._admits: Dict[int, Any] = {}   # dense: prompt_len -> admit fn
        if self.paged:
            self._chunk = jax.jit(
                ST.make_prefill_chunk_step(model, self.mesh_ctx),
                donate_argnums=(1,))
            self._first = jax.jit(self._make_first_token())
            self._set_slot = jax.jit(self._make_set_slot(),
                                     donate_argnums=(0,))

    # -- device state ------------------------------------------------------
    def _init_pool(self):
        if self.paged:
            cache = self.model.init_paged_cache(self.n_blocks, self.block_len,
                                                self.cache_dtype)
        else:
            cache = self.model.init_cache(self.n_slots, self.max_len,
                                          self.cache_dtype)
        if self.mesh is not None and self.plan is not None:
            cshapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cache)
            csh = PL.cache_shardings(self.plan, self.mesh, cshapes,
                                     self.n_blocks if self.paged
                                     else self.n_slots)
            cache = jax.device_put(cache, csh)
        n = self.n_slots
        slots = {
            "tokens": jnp.zeros((n,), jnp.int32),
            "pos": jnp.zeros((n,), jnp.int32),
            "active": jnp.zeros((n,), bool),
            "n_gen": jnp.zeros((n,), jnp.int32),
            "max_gen": jnp.ones((n,), jnp.int32),
            "eos": jnp.full((n,), -1, jnp.int32),
            "key": jnp.zeros((n, 2), jnp.uint32),
            "temperature": jnp.zeros((n,), jnp.float32),
            "top_k": jnp.zeros((n,), jnp.int32),
            "top_p": jnp.ones((n,), jnp.float32),
        }
        return cache, slots

    def _reset_paging(self):
        """Fresh allocator / radix tree / page table for one ``run``."""
        self._alloc = BlockAllocator(self.n_blocks)
        self._radix = RadixPrefixIndex(self.block_len, self._alloc)
        self._pt = np.full((self.n_slots, self.max_pages), -1, np.int32)
        self._pt_dev = None                  # lazily refreshed device copy
        self._req_blocks: Dict[int, List[int]] = {}   # rid -> mapped blocks

    def _pages_dev(self):
        if self._pt_dev is None:
            self._pt_dev = jnp.asarray(self._pt)
        return self._pt_dev

    # -- jitted helpers (paged admission) ----------------------------------
    def _make_first_token(self):
        """Sample generation index 0 from the final chunk's logits (the
        same head the dense admit fuses into ``prefill_into``)."""
        greedy = self.greedy

        @scope(SAMPLE)
        def first_token(logits, key, temperature, top_k, top_p):
            if greedy:
                return jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
            k0 = jax.random.fold_in(key, 0)
            return sample_tokens(logits, k0[None], temperature[None],
                                 top_k[None], top_p[None])[0]

        return first_token

    def _make_set_slot(self):
        def set_slot(slots, slot, tok, pos, active, max_gen, eos, key,
                     temperature, top_k, top_p):
            return {
                "tokens": slots["tokens"].at[slot].set(tok),
                "pos": slots["pos"].at[slot].set(pos),
                "active": slots["active"].at[slot].set(active),
                "n_gen": slots["n_gen"].at[slot].set(1),
                "max_gen": slots["max_gen"].at[slot].set(max_gen),
                "eos": slots["eos"].at[slot].set(eos),
                "key": slots["key"].at[slot].set(key),
                "temperature": slots["temperature"].at[slot].set(temperature),
                "top_k": slots["top_k"].at[slot].set(top_k),
                "top_p": slots["top_p"].at[slot].set(top_p),
            }

        return set_slot

    def _admit_fn(self, prompt_len: int):
        """Dense mode: one compiled admission per prompt length."""
        fn = self._admits.get(prompt_len)
        if fn is not None:
            return fn
        model, max_len, cache_dtype = self.model, self.max_len, self.cache_dtype
        mesh_ctx, greedy = self.mesh_ctx, self.greedy

        def admit(params, cache, slots, prompt, slot, key, temperature,
                  top_k, top_p, max_gen, eos):
            logits, cache = model.prefill_into(
                params, {"tokens": prompt[None]}, cache, slot,
                max_len=max_len, cache_dtype=cache_dtype, mesh_ctx=mesh_ctx)
            if greedy:   # sampler-free, like the greedy tick
                tok = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
            else:
                k0 = jax.random.fold_in(key, 0)   # generation index 0
                tok = sample_tokens(logits, k0[None], temperature[None],
                                    top_k[None], top_p[None])[0]
            finished = (tok == eos) | (max_gen <= 1)
            new_slots = {
                "tokens": slots["tokens"].at[slot].set(tok),
                "pos": slots["pos"].at[slot].set(prompt.shape[0]),
                "active": slots["active"].at[slot].set(~finished),
                "n_gen": slots["n_gen"].at[slot].set(1),
                "max_gen": slots["max_gen"].at[slot].set(max_gen),
                "eos": slots["eos"].at[slot].set(eos),
                "key": slots["key"].at[slot].set(key),
                "temperature": slots["temperature"].at[slot].set(temperature),
                "top_k": slots["top_k"].at[slot].set(top_k),
                "top_p": slots["top_p"].at[slot].set(top_p),
            }
            return cache, new_slots, tok, finished

        fn = jax.jit(admit, donate_argnums=(1, 2))
        self._admits[prompt_len] = fn
        return fn

    def _budget(self, r: Request) -> int:
        P = r.prompt_len
        if P < 1 or P >= self.max_len:
            raise EngineError(
                f"request {r.rid}: prompt_len {P} does not fit "
                f"max_len {self.max_len}")
        if self.greedy and r.temperature > 0:
            raise EngineError(
                f"request {r.rid}: temperature {r.temperature} on a "
                f"greedy-tick engine (built with greedy=True)")
        return min(int(r.max_new), self.max_len - P)

    def _warmup(self, prompt_lens) -> float:
        """Compile every jitted path a trace will hit against a sacrificial
        pool, so the timed loop measures serving, not XLA.  Paged mode
        compiles a fixed set (chunk + first-token + slot-write + tick) no
        matter how many distinct prompt lengths the trace has; dense mode
        compiles one admit per length.  Dispatch-cache hits make a second
        run's warmup just a few fast real calls."""
        t0 = time.perf_counter()
        cache, slots = self._init_pool()
        if self.paged:
            row = jnp.zeros((self.max_pages,), jnp.int32)
            logits, cache = self._chunk(
                self.params, cache, row,
                jnp.zeros((self.prefill_chunk,), jnp.int32),
                jnp.int32(0), jnp.int32(1))
            tok = self._first(logits, request_key(0), jnp.float32(0.0),
                              jnp.int32(0), jnp.float32(1.0))
            slots = self._set_slot(
                slots, jnp.int32(0), tok, jnp.int32(1), jnp.asarray(True),
                jnp.int32(1), jnp.int32(-1), request_key(0),
                jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0))
            out = self._tick(self.params, cache, slots,
                             jnp.zeros((self.n_slots, self.max_pages),
                                       jnp.int32))
        else:
            for P in sorted(set(prompt_lens)):
                admit = self._admit_fn(P)
                cache, slots, _, _ = admit(
                    self.params, cache, slots, jnp.zeros((P,), jnp.int32),
                    jnp.int32(0), request_key(0), jnp.float32(0.0),
                    jnp.int32(0), jnp.float32(1.0), jnp.int32(1),
                    jnp.int32(-1))
            out = self._tick(self.params, cache, slots)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    # -- the scheduler loop ------------------------------------------------
    def run(self, requests: Sequence[Request], *, realtime: bool = True,
            warmup: bool = True) -> Dict[str, Any]:
        """Serve a trace to completion; returns per-request rows + metrics.

        ``realtime=False`` ignores arrival offsets (closed loop, maximum
        pressure — the bench mode).  Metrics: TTFT (arrival -> first token,
        queueing included; split hit/cold in paged mode), decode-tick time
        percentiles (``tick_ms``, each tick once), tokens/s, slot
        utilization, and — paged —
        prefix-cache hit rate plus allocator/eviction counters.  The first
        token of every request is sampled from the prefill logits and
        accounted to prefill/TTFT; only subsequent tokens count as decode
        throughput.  ``warmup`` (default) compiles every path against a
        sacrificial pool first, so compile time lands in ``compile_s``
        instead of polluting every latency and throughput number.
        """
        pending = deque(sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        budgets = {r.rid: self._budget(r) for r in pending}
        compile_s = (self._warmup([r.prompt_len for r in pending])
                     if warmup else 0.0)
        cache, slots = self._init_pool()
        if self.paged:
            self._reset_paging()
        free: List[int] = list(range(self.n_slots))[::-1]
        slot_req: Dict[int, Request] = {}
        streams: Dict[int, List[int]] = {}
        rows: Dict[int, Dict[str, Any]] = {}
        ttfts: List[float] = []
        tick_s: List[float] = []
        ticks = 0
        busy_slot_ticks = 0
        prefill_s = 0.0
        decode_s = 0.0
        interleaved_ticks = 0
        cached_prompt_tokens = 0
        total_prompt_tokens = 0
        timeouts = 0
        tel = self.telemetry
        do_spans = tel is not None and getattr(tel, "spans", False)
        # (t_admit_begin, t_first_token) per rid, absolute perf_counter
        # readings — the span anchors emitted when the request retires
        span_times: Dict[int, Any] = {}
        # one occupancy sample per decode tick: queue depth, busy slots,
        # and (paged) free pool blocks — the raw series behind the serve
        # bench's queue-depth / slot-occupancy timeline
        timeline: List[Dict[str, Any]] = []
        # deadlines cost a scan per loop iteration — skip it entirely for
        # the (default) deadline-free workload
        deadlines_on = self.deadline_s > 0 or any(
            getattr(r, "deadline_s", 0.0) > 0 for r in pending)

        def req_expiry(r: Request):
            """Absolute wall time (vs t0) this request must finish by."""
            dl = getattr(r, "deadline_s", 0.0) or self.deadline_s
            if dl <= 0:
                return None
            return (r.arrival_s if realtime else 0.0) + dl

        t0 = time.perf_counter()

        def retire(slot: int, r: Request, finish: str = "") -> None:
            stream = streams[r.rid]
            t_ret = time.perf_counter()
            rows[r.rid].update(
                n_gen=len(stream),
                gen_ids=stream,
                finish=finish or ("eos" if r.eos_id >= 0
                                  and stream[-1] == r.eos_id
                                  else "length"),
                done_s=round(t_ret - t0, 6),
            )
            if do_spans:
                anchors = span_times.pop(r.rid, None)
                if anchors is not None:
                    t_adm, t_first = anchors
                    t_arr = t0 + rows[r.rid]["arrival_s"]
                    row = rows[r.rid]
                    req = tel.span_row(
                        "serve/request", t_arr, t_ret, rid=r.rid, slot=slot,
                        prompt_len=r.prompt_len, n_gen=len(stream),
                        finish=row["finish"])
                    tel.span_row("serve/queued", t_arr, t_adm,
                                 parent=req, rid=r.rid)
                    tel.span_row("serve/prefill", t_adm, t_first,
                                 parent=req, rid=r.rid,
                                 cached_tokens=row.get("cached_tokens", 0),
                                 chunks=row.get("prefill_chunks", 0))
                    tel.span_row("serve/decode", t_first, t_ret,
                                 parent=req, rid=r.rid)
            else:
                span_times.pop(r.rid, None)
            slot_req.pop(slot, None)
            free.append(slot)
            if self.paged:
                # drop this request's references; pages also held by the
                # radix tree survive for future prefix hits, private tail
                # pages free immediately
                blocks = self._req_blocks.pop(r.rid, None)
                if blocks:
                    self._alloc.release(blocks)
                self._pt[slot, :] = -1
                self._pt_dev = None

        def do_tick() -> None:
            nonlocal cache, slots, ticks, busy_slot_ticks, decode_s
            ta = time.perf_counter()
            if self.paged:
                with span("serve/page_table"):
                    extra = (self._pages_dev(),)
            else:
                extra = ()
            with span("serve/tick"):
                if self.fault_injector is not None:
                    stall = self.fault_injector.fire("serve_stall")
                    if stall is not None and stall.seconds > 0:
                        # a hung collective, simulated
                        time.sleep(stall.seconds)
                cache, slots, sampled, finished = self._tick(
                    self.params, cache, slots, *extra)
                sampled, finished = jax.device_get((sampled, finished))
            dt = time.perf_counter() - ta
            if self.watchdog_s > 0 and dt > self.watchdog_s:
                raise EngineError(
                    f"no-progress watchdog: tick {ticks + 1} took {dt:.3f}s "
                    f"(> watchdog_s={self.watchdog_s}) with "
                    f"{len(slot_req)} request(s) in flight")
            with span("serve/schedule"):
                decode_s += dt
                tick_s.append(dt)
                ticks += 1
                busy_slot_ticks += len(slot_req)
                for slot in list(slot_req):
                    r = slot_req[slot]
                    streams[r.rid].append(int(sampled[slot]))
                    if bool(finished[slot]):
                        retire(slot, r)
                if len(timeline) < 100_000:
                    sample = {"t_s": round(time.perf_counter() - t0, 6),
                              "queue": len(pending), "busy": len(slot_req)}
                    if self.paged:
                        sample["free_blocks"] = int(self._alloc.n_free)
                    timeline.append(sample)

        def admit_dense(r: Request) -> None:
            nonlocal cache, slots, prefill_s
            slot = free.pop()
            admit = self._admit_fn(r.prompt_len)
            ta = time.perf_counter()
            with span("serve/prefill_chunk"):   # the whole prompt, one chunk
                cache, slots, tok, fin = admit(
                    self.params, cache, slots,
                    jnp.asarray(r.prompt, jnp.int32),
                    jnp.int32(slot), request_key(r.seed),
                    jnp.float32(r.temperature), jnp.int32(r.top_k),
                    jnp.float32(r.top_p), jnp.int32(budgets[r.rid]),
                    jnp.int32(r.eos_id))
                tok, fin = jax.device_get((tok, fin))
            tb = time.perf_counter()
            prefill_s += tb - ta
            finish_admission(r, slot, int(tok), bool(fin), tb - ta, tb,
                             cached=0, n_chunks=1, t_admit0=ta)

        def admit_paged(r: Request) -> bool:
            """Map pages, prefill the un-cached tail in fixed-size chunks
            (interleaving one decode tick between chunks so co-resident
            streams never stall longer than one chunk), sample the first
            token, and publish the prompt's full pages to the radix tree.
            Returns False when the pool cannot hold the request yet."""
            nonlocal cache, slots, prefill_s, interleaved_ticks
            nonlocal cached_prompt_tokens, total_prompt_tokens
            P, budget = r.prompt_len, budgets[r.rid]
            bl, C = self.block_len, self.prefill_chunk
            prompt = [int(t) for t in r.prompt]
            n_pages_req = -(-(P + budget) // bl)
            matched = []
            if self.prefix_cache:
                # match whole pages, capped one token short of the prompt
                # (the last token must be recomputed for first-token logits)
                # and floored to the chunk grid: the un-cached tail then
                # starts exactly where a cold prefill's chunk would, which
                # is what keeps hit == cold bitwise
                matched = self._radix.match(prompt, ((P - 1) // C) * C)
                keep = (len(matched) * bl // C) * C // bl
                matched = matched[:keep]
            n_fresh = n_pages_req - len(matched)
            if n_fresh > self._alloc.n_free:
                self._radix.evict(n_fresh)
            if n_fresh > self._alloc.n_free:
                if not slot_req:
                    raise EngineError(
                        f"request {r.rid}: needs {n_fresh} blocks, "
                        f"{self._alloc.n_free}/{self.n_blocks} free with no "
                        f"requests in flight — pool too small")
                return False        # wait for a retirement
            ta = time.perf_counter()
            t_adm0 = ta             # admission begin (ta moves per chunk)
            for node in matched:
                self._alloc.retain(node.block)
            blocks = [n.block for n in matched] + self._alloc.alloc(n_fresh)
            slot = free.pop()
            self._pt[slot, :] = -1
            self._pt[slot, :len(blocks)] = blocks
            self._pt_dev = None
            self._req_blocks[r.rid] = blocks
            with span("serve/page_table"):
                row_dev = jnp.asarray(self._pt[slot])
            S = len(matched) * bl
            cached_prompt_tokens += S
            total_prompt_tokens += P
            n_chunks = -(-(P - S) // C)
            logits = None
            for ci in range(n_chunks):
                lo = S + ci * C
                seg = prompt[lo:min(lo + C, P)]
                toks = np.zeros((C,), np.int32)
                toks[:len(seg)] = seg
                with span("serve/prefill_chunk"):
                    logits, cache = self._chunk(
                        self.params, cache, row_dev, jnp.asarray(toks),
                        jnp.int32(lo), jnp.int32(len(seg)))
                if ci < n_chunks - 1 and slot_req:
                    prefill_s += time.perf_counter() - ta
                    do_tick()       # co-residents advance between chunks
                    interleaved_ticks += 1
                    ta = time.perf_counter()
            with span("serve/first_token"):
                tok = int(jax.device_get(self._first(
                    logits, request_key(r.seed), jnp.float32(r.temperature),
                    jnp.int32(r.top_k), jnp.float32(r.top_p))))
            fin = (r.eos_id >= 0 and tok == r.eos_id) or budget <= 1
            with span("serve/slot_write"):
                slots = self._set_slot(
                    slots, jnp.int32(slot), jnp.int32(tok), jnp.int32(P),
                    jnp.asarray(not fin), jnp.int32(budget),
                    jnp.int32(r.eos_id), request_key(r.seed),
                    jnp.float32(r.temperature), jnp.int32(r.top_k),
                    jnp.float32(r.top_p))
            tb = time.perf_counter()
            prefill_s += tb - ta
            if self.prefix_cache:
                # publish the prompt's full pages (chunk-written, canonical);
                # existing nodes win, so a re-derived duplicate page stays
                # private and frees at retire
                self._radix.insert(prompt[:(P // bl) * bl], blocks)
            finish_admission(r, slot, tok, fin, tb - ta, tb,
                             cached=S, n_chunks=n_chunks, t_admit0=t_adm0)
            return True

        def finish_admission(r, slot, tok, fin, admit_s, tb, *, cached,
                             n_chunks, t_admit0):
            arrival = r.arrival_s if realtime else 0.0
            ttft = tb - t0 - arrival
            ttfts.append(ttft)
            streams[r.rid] = [tok]
            # queue_s is the span the request sat unadmitted (arrival ->
            # admission begin): with prefill_s it decomposes TTFT into
            # queueing vs compute (interleaved decode ticks during a
            # chunked admission account for any remainder)
            queue_s = max(0.0, (t_admit0 - t0) - arrival)
            rows[r.rid] = {
                "id": r.rid, "slot": slot, "prompt_len": r.prompt_len,
                "max_new": budgets[r.rid], "arrival_s": arrival,
                "ttft_s": round(ttft, 6),
                "queue_s": round(queue_s, 6),
                "prefill_s": round(admit_s, 6),
                "cached_tokens": cached,
                "prefill_chunks": n_chunks,
            }
            span_times[r.rid] = (t_admit0, tb)
            slot_req[slot] = r
            if fin:
                retire(slot, r)

        while pending or slot_req:
            with span("serve/schedule"):
                now = time.perf_counter() - t0
                if deadlines_on and pending:
                    # queued requests past their deadline retire unserved —
                    # admitting them would spend prefill on a dead answer
                    keep: deque = deque()
                    for r in pending:
                        exp = req_expiry(r)
                        if exp is not None and now > exp:
                            rows[r.rid] = {
                                "id": r.rid, "slot": -1,
                                "prompt_len": r.prompt_len,
                                "max_new": budgets[r.rid],
                                "arrival_s": r.arrival_s if realtime else 0.0,
                                "cached_tokens": 0, "prefill_chunks": 0,
                                "n_gen": 0, "gen_ids": [],
                                "finish": "timeout",
                                "done_s": round(now, 6),
                            }
                            timeouts += 1
                        else:
                            keep.append(r)
                    pending = keep
                while free and pending and (not realtime
                                            or pending[0].arrival_s <= now):
                    r = pending[0]
                    if self.paged:
                        if not admit_paged(r):
                            break
                    else:
                        admit_dense(r)
                    pending.popleft()
                    now = time.perf_counter() - t0
            if not slot_req:
                if pending and realtime:
                    with span("serve/idle"):
                        time.sleep(min(max(pending[0].arrival_s - now, 0.0),
                                       0.05))
                continue
            do_tick()
            if deadlines_on and slot_req:
                with span("serve/schedule"):
                    now = time.perf_counter() - t0
                    for slot in list(slot_req):
                        r = slot_req[slot]
                        exp = req_expiry(r)
                        if exp is not None and now > exp \
                                and "n_gen" not in rows[r.rid]:
                            retire(slot, r, finish="timeout")
                            timeouts += 1

        elapsed = time.perf_counter() - t0
        gen_tokens = sum(len(s) for s in streams.values())
        decode_tokens = gen_tokens - len(streams)   # firsts belong to prefill
        util = (busy_slot_ticks / (ticks * self.n_slots)) if ticks else 0.0
        decode_tok_s = decode_tokens / decode_s if decode_s > 0 else 0.0
        # queued-expired rows were never admitted (no prefill/ttft sample)
        admitted = [w for w in rows.values() if "prefill_s" in w]
        hit = [w for w in admitted if w["cached_tokens"] > 0]
        cold = [w for w in admitted if w["cached_tokens"] == 0]
        result: Dict[str, Any] = {
            "n_slots": self.n_slots,
            "max_len": self.max_len,
            "n_requests": len(rows),
            "completed": sum(1 for row in rows.values()
                             if row.get("finish") in ("eos", "length")),
            "timeouts": timeouts,
            "generated_tokens": gen_tokens,
            "decode_tokens": decode_tokens,
            "compile_s": round(compile_s, 4),
            "elapsed_s": round(elapsed, 4),
            "prefill_s": round(prefill_s, 4),
            "decode_s": round(decode_s, 4),
            "ticks": ticks,
            "tok_s": int(gen_tokens / elapsed) if elapsed > 0 else 0,
            "decode_tok_s": int(decode_tok_s),
            # occupancy-normalized: what decode throughput would be at 100%
            # slot occupancy — the apples-to-apples number vs a static batch
            "decode_tok_s_full": int(decode_tok_s / util) if util > 0 else 0,
            "slot_utilization": round(util, 4),
            "ttft_s": percentiles(ttfts),
            # TTFT split: time queued (arrival -> admission begin) vs time
            # in prefill compute — from the per-request lifecycle anchors
            "queue_s": percentiles([w["queue_s"] for w in admitted
                                    if "queue_s" in w]),
            # each decode tick's wall time, dispatch to tokens on the host,
            # once per tick (a token's latency is one tick per slot)
            "tick_ms": percentiles([t * 1000 for t in tick_s]),
            # hit/cold split: prefix-cache hits should beat cold prefills on
            # both the queue-free admission time and end-to-end TTFT
            "prefill_cache_hit_rate": (
                round(cached_prompt_tokens / total_prompt_tokens, 4)
                if total_prompt_tokens else 0.0),
            "ttft_hit_s": percentiles([w["ttft_s"] for w in hit]),
            "ttft_cold_s": percentiles([w["ttft_s"] for w in cold]),
            "prefill_hit_s": percentiles([w["prefill_s"] for w in hit]),
            "prefill_cold_s": percentiles([w["prefill_s"] for w in cold]),
            "interleaved_decode_ticks": interleaved_ticks,
            "timeline": timeline,
            "requests": [rows[rid] for rid in sorted(rows)],
        }
        if self.paged:
            result["paging"] = {
                "block_len": self.block_len,
                "n_blocks": self.n_blocks,
                "max_pages": self.max_pages,
                "prefill_chunk": self.prefill_chunk,
                "prefix_cache": self.prefix_cache,
                "peak_blocks": int(self._alloc.peak_used),
                "free_blocks": int(self._alloc.n_free),
                "cached_blocks": int(self._radix.n_nodes),
                "evictions": int(self._radix.evictions),
            }
        if tel is not None:
            headline = {
                "tok_s": result["tok_s"],
                "decode_tok_s": result["decode_tok_s"],
                "slot_utilization": result["slot_utilization"],
                "completed": result["completed"],
                "ticks": ticks,
            }
            for key in ("ttft_s", "queue_s", "tick_ms"):
                p = result.get(key) or {}
                if isinstance(p, dict) and "p50" in p:
                    headline[f"{key}_p50"] = p["p50"]
            tel.metric(None, headline, phase="serve_summary")
        self.log(
            f"engine: {result['n_requests']} requests, "
            f"{gen_tokens} tokens in {elapsed:.3f}s "
            f"({result['tok_s']} tok/s, decode {result['decode_tok_s']} "
            f"tok/s, util {util:.0%}, "
            f"hit rate {result['prefill_cache_hit_rate']:.0%})")
        return result
