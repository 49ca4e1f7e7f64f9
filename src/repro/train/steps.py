"""SPMD step functions: train / prefill / decode — what the gym drives and
what the dry-run lowers."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import base as B
from ..models.common import sharded_cross_entropy
from ..telemetry.spans import LOSS, OPTIMIZER, SAMPLE, scope


def compute_loss(model, params, batch, mesh_ctx=None, storage_axes=(),
                 mtp_coef: float = 0.3):
    logits, aux = model.apply(params, batch, mesh_ctx, storage_axes)
    cfg = model.cfg
    if cfg.n_patches:
        logits = logits[:, cfg.n_patches:]
    mask = batch.get("loss_mask")
    with scope(LOSS):
        loss = sharded_cross_entropy(logits, batch["labels"], mask)
    total = loss
    if "router_lb" in aux:
        total = total + aux["router_lb"]
    if "mtp" in aux:
        total = total + mtp_coef * aux["mtp"]
    return total, {"ce": loss, **aux}


def make_train_step(model, optimizer, mesh_ctx: Optional[B.MeshContext] = None,
                    storage_axes: Tuple[str, ...] = (), grad_accum: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    When ``mesh_ctx.pp > 1`` the model's backbone runs the pipelined
    stage/microbatch schedule internally (``sharding.pipeline``); the loss
    is still computed once over the full (re-assembled) batch, so
    ``jax.grad`` transposes the schedule into the pipelined backward and
    the pipeline's per-microbatch gradient contributions accumulate inside
    autodiff. ``grad_accum`` composes orthogonally on top: each accum
    chunk is itself pipelined, and the explicit accumulation below keeps
    the ≥f32 carry either way."""
    from ..sharding import pipeline as PIPE

    def loss_fn(params, batch):
        return compute_loss(model, params, batch, mesh_ctx, storage_axes)

    def train_step(state, batch):
        if grad_accum > 1:
            def micro(carry, mb):
                gsum, msum = carry
                (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], mb
                )
                gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
                msum = jax.tree_util.tree_map(jnp.add, msum, metrics)
                return (gsum, msum), None

            mbs = PIPE.microbatch(batch, grad_accum)
            # accumulator structure comes from what value_and_grad actually
            # produces (eval_shape), but gradients accumulate in >= f32: a
            # bf16 scan carry would compound 8-mantissa-bit rounding every
            # micro-step. jnp.add(f32, bf16) promotes, so the carry stays f32.
            mb0 = jax.tree_util.tree_map(lambda x: x[0], mbs)
            (_, m_shapes), g_shapes = jax.eval_shape(
                jax.value_and_grad(loss_fn, has_aux=True), state["params"], mb0
            )
            zeros_g = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, jnp.promote_types(s.dtype,
                                                               jnp.float32)),
                g_shapes)
            zeros_m = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), m_shapes)
            (grads, metrics), _ = jax.lax.scan(
                micro, (zeros_g, zeros_m), mbs
            )
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            metrics = jax.tree_util.tree_map(lambda m: m / grad_accum, metrics)
        else:
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch
            )
        with scope(OPTIMIZER):
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics)
        metrics["loss"] = metrics["ce"]
        return new_state, metrics

    return train_step


def init_train_state(model, optimizer, rng, param_dtype=None):
    params = model.init(rng)
    if param_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(param_dtype)
            if p.dtype == jnp.float32 else p, params)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_train_state(model, optimizer, rng=None, param_dtype=None):
    rng = jax.random.PRNGKey(0) if rng is None else rng
    return jax.eval_shape(
        lambda r: init_train_state(model, optimizer, r, param_dtype), rng)


def opt_state_shardings(opt_shapes, pspecs, rep):
    """Shardings for the optimizer state: moment/master trees mirror the
    param tree; scalars replicated."""
    out = {}
    for k, v in opt_shapes.items():
        out[k] = pspecs if isinstance(v, dict) or k in ("m", "v", "master") else rep
    return out


def make_prefill_step(model, mesh_ctx=None, storage_axes=()):
    def prefill_step(params, batch):
        return model.prefill(params, batch, mesh_ctx=mesh_ctx,
                             storage_axes=storage_axes)

    return prefill_step


def make_serve_step(model, mesh_ctx=None):
    """One decode iteration: next-token logits -> greedy token, updated cache."""

    def serve_step(params, cache, tokens, positions):
        logits, new_cache = model.decode_step(params, cache, tokens, positions,
                                              mesh_ctx)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, new_cache

    return serve_step


def make_engine_step(model, mesh_ctx: Optional[B.MeshContext] = None,
                     greedy: bool = False, paged: bool = False):
    """The continuous-batching decode tick (``repro.serve`` engine hot path).

    One fused step over the whole slot pool: decode every slot at its own
    position, run the on-device sampling head (greedy / temperature / top-k /
    top-p, seeded per request), and update the per-slot stop flags — a single
    jitted call with the cache and slot state donated, so steady-state decode
    never reallocates.

    ``slots`` is a dict of per-slot arrays (``n_slots`` leading dim):

    - ``tokens`` i32: last sampled token (fed to this tick's decode)
    - ``pos`` i32: absolute position ``tokens`` is written/attended at
    - ``active`` bool: slot holds a live request
    - ``n_gen`` i32: tokens generated so far (the prefill token counts)
    - ``max_gen`` i32: per-request generation budget
    - ``eos`` i32: per-request stop token (-1 disables)
    - ``key`` u32[2]: per-request PRNG base key (token t uses fold_in(key, t))
    - ``temperature``/``top_k``/``top_p``: sampling knobs per slot

    Returns ``(new_cache, new_slots, sampled, finished)``; inactive slots
    keep their token/position frozen and their sampled entry is garbage the
    scheduler never reads.

    ``greedy=True`` compiles a sampler-free tick (plain argmax — what
    ``sample_tokens`` returns for ``temperature <= 0``, minus the
    full-vocab sort/softmax/cumsum/Gumbel work XLA cannot dead-code away
    when temperature is a runtime array).  The variant is static per
    engine: a greedy tick and the general tick are different fused
    programs, so mixing them within one determinism comparison would
    reintroduce batch-shape-style low-bit drift.

    ``paged=True`` compiles the tick against a block-pool cache: it takes
    the per-slot page tables as a fourth (non-donated) argument and gates
    cache writes on ``slots["active"]`` — a retired slot's blocks may
    already be freed and remapped, so its frozen-position write must be
    dropped, not just ignored.
    """
    from ..serve.sampling import sample_tokens

    @scope(SAMPLE)
    def _sample_and_advance(slots, logits, new_cache):
        if greedy:
            sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            step_keys = jax.vmap(jax.random.fold_in)(slots["key"],
                                                     slots["n_gen"])
            sampled = sample_tokens(logits, step_keys, slots["temperature"],
                                    slots["top_k"], slots["top_p"])
        active = slots["active"]
        live = active.astype(jnp.int32)
        sampled = jnp.where(active, sampled, slots["tokens"])
        n_gen = slots["n_gen"] + live
        finished = active & ((sampled == slots["eos"])
                             | (n_gen >= slots["max_gen"]))
        new_slots = dict(
            slots,
            tokens=sampled,
            pos=slots["pos"] + live,
            n_gen=n_gen,
            active=active & ~finished,
        )
        return new_cache, new_slots, sampled, finished

    if paged:
        def engine_step(params, cache, slots, pages):
            logits, new_cache = model.decode_step(
                params, cache, slots["tokens"], slots["pos"], mesh_ctx,
                pages=pages, active=slots["active"])
            return _sample_and_advance(slots, logits, new_cache)
    else:
        def engine_step(params, cache, slots):
            logits, new_cache = model.decode_step(
                params, cache, slots["tokens"], slots["pos"], mesh_ctx)
            return _sample_and_advance(slots, logits, new_cache)

    return engine_step


def make_prefill_chunk_step(model, mesh_ctx: Optional[B.MeshContext] = None):
    """One fixed-shape chunk of a paged admission (``model.prefill_chunk``).

    The chunk program's shape depends only on (chunk_len, pool shape) —
    never on the prompt length — which is what makes a cached page's
    values bitwise canonical and a long admission splittable across decode
    ticks.  Jit with the cache donated; ``start``/``n_valid`` are traced.
    """

    def chunk_step(params, cache, pages_row, tokens, start, n_valid):
        return model.prefill_chunk(params, cache, pages_row, tokens, start,
                                   n_valid, mesh_ctx=mesh_ctx)

    return chunk_step
