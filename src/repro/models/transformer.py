"""Decoder-only LM assembled from pluggable blocks.

Layers are *stacked* ([L, ...] leaves) and consumed by ``lax.scan`` in groups
of ``cfg.scan_block_size`` layers — the JAX analog of Modalities' adaptable
FSDP unit size: each scan step all-gathers exactly one group's parameters, so
the group size dials the collective message size (paper Fig 2c).

Supports: dense (GQA/MQA, qkv-bias, sliding window), MoE (shared+routed,
leading dense layers, optional MTP head), MLA, SSM (Mamba2), and hybrid
(Mamba2 + a weight-shared attention block every ``attn_every`` layers,
Zamba2-style).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import base as B
from . import mlp as M
from . import moe as MOE
from . import ssm as S
from . import stacked as ST
from .common import apply_norm, embed_init, norm_axes, norm_params, softmax_cross_entropy, sharded_cross_entropy
from ..telemetry.spans import ATTENTION, EMBED, HEAD, MLP, SSM, scope


# ---------------------------------------------------------------------------
# per-layer init / axes / apply
# ---------------------------------------------------------------------------
def _layer_kind(cfg: B.ArchConfig, i: int) -> str:
    if cfg.arch_type == "ssm":
        return "ssm"
    if cfg.arch_type == "hybrid":
        return "attn_block" if (i + 1) % cfg.attn_every == 0 else "ssm"
    if cfg.arch_type == "moe" and i >= cfg.moe.n_dense_layers:
        return "moe_block"
    return "dense_block"


def init_dense_block(cfg: B.ArchConfig, rng):
    r1, r2 = jax.random.split(rng)
    attn = A.init_mla(cfg, r1) if cfg.mla else A.init_gqa(cfg, r1)
    return {
        "attn_norm": norm_params(cfg),
        "attn": attn,
        "mlp_norm": norm_params(cfg),
        "mlp": M.init_mlp(cfg, r2),
    }


def dense_block_axes(cfg: B.ArchConfig):
    return {
        "attn_norm": norm_axes(cfg),
        "attn": A.mla_axes(cfg) if cfg.mla else A.gqa_axes(cfg),
        "mlp_norm": norm_axes(cfg),
        "mlp": M.mlp_axes(cfg),
    }


def init_moe_block(cfg: B.ArchConfig, rng):
    r1, r2 = jax.random.split(rng)
    attn = A.init_mla(cfg, r1) if cfg.mla else A.init_gqa(cfg, r1)
    return {
        "attn_norm": norm_params(cfg),
        "attn": attn,
        "mlp_norm": norm_params(cfg),
        "moe": MOE.init_moe(cfg, r2),
    }


def moe_block_axes(cfg: B.ArchConfig):
    return {
        "attn_norm": norm_axes(cfg),
        "attn": A.mla_axes(cfg) if cfg.mla else A.gqa_axes(cfg),
        "mlp_norm": norm_axes(cfg),
        "moe": MOE.moe_axes(cfg),
    }


def init_ssm_block(cfg: B.ArchConfig, rng):
    return {"norm": norm_params(cfg), "ssm": S.init_ssm(cfg, rng)}


def ssm_block_axes(cfg: B.ArchConfig):
    return {"norm": norm_axes(cfg), "ssm": S.ssm_axes(cfg)}


def _mlp_residual(cfg, kind, p, x, mesh_ctx=None, storage_axes=()):
    """``x`` plus the MLP (or MoE) sub-block of its norm; returns (x, aux)."""
    with scope(MLP):
        h = apply_norm(cfg, p["mlp_norm"], x)
        if kind == "moe_block":
            h, aux = MOE.moe_forward(cfg, p["moe"], h, mesh_ctx, storage_axes)
        else:
            h = M.mlp_forward(cfg, p["mlp"], h)
            aux = jnp.zeros((), jnp.float32)
        return x + h, aux


def apply_block(cfg, kind, p, x, positions, mesh_ctx, storage_axes=()):
    """Residual block; returns (x, aux)."""
    x = B.constrain(x, mesh_ctx)
    if kind == "ssm":
        with scope(SSM):
            x = x + S.ssm_forward(cfg, p["ssm"],
                                  apply_norm(cfg, p["norm"], x))
        return x, jnp.zeros((), jnp.float32)
    with scope(ATTENTION):
        h = apply_norm(cfg, p["attn_norm"], x)
        if cfg.mla:
            h = A.mla_forward(cfg, p["attn"], h, positions)
        else:
            h = A.gqa_forward(cfg, p["attn"], h, positions)
        x = x + h
    x, aux = _mlp_residual(cfg, kind, p, x, mesh_ctx, storage_axes)
    return B.constrain(x, mesh_ctx), aux


def decode_block(cfg, kind, p, cache, x, positions, mesh_ctx=None,
                 storage_axes=()):
    if kind == "ssm":
        with scope(SSM):
            h, new_cache = S.ssm_decode(cfg, p["ssm"], cache,
                                        apply_norm(cfg, p["norm"], x))
            x = x + h
        return x, new_cache, jnp.zeros((), jnp.float32)
    with scope(ATTENTION):
        h = apply_norm(cfg, p["attn_norm"], x)
        if cfg.mla:
            h, new_cache = A.mla_decode(cfg, p["attn"], cache, h, positions,
                                        absorb=cfg.mla_absorb)
        else:
            h, new_cache = A.gqa_decode(cfg, p["attn"], cache, h, positions)
        x = x + h
    x, _ = _mlp_residual(cfg, kind, p, x, mesh_ctx, storage_axes)
    return x, new_cache, jnp.zeros((), jnp.float32)


def decode_block_paged(cfg, kind, p, cache, layer, x, positions, pages,
                       active, mesh_ctx=None, storage_axes=()):
    """``decode_block`` reading/writing K/V through page tables, at layer
    ``layer`` of the full-depth pool ``cache``."""
    with scope(ATTENTION):
        h = apply_norm(cfg, p["attn_norm"], x)
        if cfg.mla:
            h, new_cache = A.mla_decode_paged(cfg, p["attn"], cache, layer,
                                              h, positions, pages, active,
                                              absorb=cfg.mla_absorb)
        else:
            h, new_cache = A.gqa_decode_paged(cfg, p["attn"], cache, layer,
                                              h, positions, pages, active)
        x = x + h
    x, _ = _mlp_residual(cfg, kind, p, x, mesh_ctx, storage_axes)
    return x, new_cache


def prefill_chunk_block(cfg, kind, p, cache, layer, x, positions, pages_row,
                        n_valid, mesh_ctx=None, storage_axes=()):
    """One layer of the fixed-shape chunked-prefill program, at layer
    ``layer`` of the full-depth pool ``cache``."""
    x = B.constrain(x, mesh_ctx)
    with scope(ATTENTION):
        h = apply_norm(cfg, p["attn_norm"], x)
        if cfg.mla:
            h, new_cache = A.mla_prefill_chunk(cfg, p["attn"], cache, layer,
                                               h, positions, pages_row,
                                               n_valid)
        else:
            h, new_cache = A.gqa_prefill_chunk(cfg, p["attn"], cache, layer,
                                               h, positions, pages_row,
                                               n_valid)
        x = x + h
    x, _ = _mlp_residual(cfg, kind, p, x, mesh_ctx, storage_axes)
    return B.constrain(x, mesh_ctx), new_cache


def _pad_cache_seq(k, max_len, window):
    """k [B,S,...] -> cache layout [B,L,...] (ring-packed when windowed)."""
    S = k.shape[1]
    if window and window > 0:
        L = min(max_len, window)
        take = min(S, L)
        tail = k[:, S - take:]
        if S <= L:
            slots = jnp.arange(take)
        else:
            slots = (jnp.arange(S - take, S)) % L
        out = jnp.zeros((k.shape[0], L) + k.shape[2:], k.dtype)
        return out.at[:, slots].set(tail)
    if S >= max_len:
        return k[:, :max_len]
    pad = [(0, 0), (0, max_len - S)] + [(0, 0)] * (k.ndim - 2)
    return jnp.pad(k, pad)


def prefill_block(cfg, kind, p, x, positions, max_len, cache_dtype, mesh_ctx=None,
                  storage_axes=()):
    """Like apply_block but also returns the decode-ready cache leaf."""
    x = B.constrain(x, mesh_ctx)
    if kind == "ssm":
        with scope(SSM):
            h, st = S.ssm_forward(cfg, p["ssm"], apply_norm(cfg, p["norm"], x),
                                  return_state=True)
            x = x + h
        return x, st
    with scope(ATTENTION):
        h = apply_norm(cfg, p["attn_norm"], x)
        if cfg.mla:
            h, (c_kv, k_rope) = A.mla_forward(cfg, p["attn"], h, positions,
                                              return_latent=True)
            cache = {
                "c_kv": _pad_cache_seq(c_kv.astype(cache_dtype), max_len, 0),
                "k_rope": _pad_cache_seq(k_rope.astype(cache_dtype), max_len,
                                         0),
            }
        else:
            h, (k, v) = A.gqa_forward(cfg, p["attn"], h, positions,
                                      return_kv=True)
            cache = {
                "k": _pad_cache_seq(k.astype(cache_dtype), max_len,
                                    cfg.window),
                "v": _pad_cache_seq(v.astype(cache_dtype), max_len,
                                    cfg.window),
            }
        x = x + h
    x, _ = _mlp_residual(cfg, kind, p, x, mesh_ctx, storage_axes)
    return B.constrain(x, mesh_ctx), cache


def init_cache_block(cfg, kind, batch, max_len, dtype):
    if kind == "ssm":
        return S.ssm_init_state(cfg, batch)
    if cfg.mla:
        return A.mla_init_cache(cfg, batch, max_len, dtype)
    return A.gqa_init_cache(cfg, batch, max_len, dtype)


# ---------------------------------------------------------------------------
# stacked init helpers
# ---------------------------------------------------------------------------
_stack_init = ST.stack_init
_take_layer = ST.take_layer


def _with_layer_axis(axes_tree):
    return jax.tree_util.tree_map(
        lambda t: (B.LAYER,) + tuple(t), axes_tree, is_leaf=lambda t: isinstance(t, tuple)
    )


class DecoderLM(B.Model):
    """Decoder-only language model (dense / moe / ssm / hybrid / vlm)."""

    def __init__(self, cfg: B.ArchConfig):
        super().__init__(cfg)
        self.kinds = [_layer_kind(cfg, i) for i in range(cfg.n_layers)]

    # -- structure ---------------------------------------------------------
    def _stacks(self):
        """Return list of (name, kind, layer_indices) homogeneous stacks."""
        cfg = self.cfg
        if cfg.arch_type == "hybrid":
            ssm_idx = [i for i, k in enumerate(self.kinds) if k == "ssm"]
            return [("ssm_blocks", "ssm", ssm_idx)]
        if cfg.arch_type == "moe" and cfg.moe.n_dense_layers:
            nd = cfg.moe.n_dense_layers
            return [
                ("dense_blocks", "dense_block", list(range(nd))),
                ("moe_blocks", "moe_block", list(range(nd, cfg.n_layers))),
            ]
        kind = self.kinds[0]
        name = {"dense_block": "blocks", "moe_block": "moe_blocks", "ssm": "ssm_blocks"}[kind]
        return [(name, kind, list(range(cfg.n_layers)))]

    def init(self, rng):
        cfg = self.cfg
        r_embed, r_head, r_blocks, r_shared, r_mtp = jax.random.split(rng, 5)
        p: Dict[str, Any] = {
            "embed": embed_init(r_embed, (cfg.vocab, cfg.d_model)),
            "final_norm": norm_params(cfg),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(r_head, (cfg.d_model, cfg.vocab))
        init_by_kind = {
            "dense_block": functools.partial(init_dense_block, cfg),
            "moe_block": functools.partial(init_moe_block, cfg),
            "ssm": functools.partial(init_ssm_block, cfg),
        }
        rs = jax.random.split(r_blocks, len(self._stacks()))
        for (name, kind, idxs), r in zip(self._stacks(), rs):
            p[name] = _stack_init(init_by_kind[kind], r, len(idxs))
        if cfg.arch_type == "hybrid":
            p["shared_attn"] = init_dense_block(cfg, r_shared)
        if cfg.mtp:
            p["mtp"] = {
                "proj": embed_init(r_mtp, (2 * cfg.d_model, cfg.d_model)),
                "block": init_dense_block(cfg, r_mtp),
                "norm": norm_params(cfg),
            }
        return p

    def param_axes(self):
        cfg = self.cfg
        axes_by_kind = {
            "dense_block": dense_block_axes,
            "moe_block": moe_block_axes,
            "ssm": ssm_block_axes,
        }
        p: Dict[str, Any] = {
            "embed": (B.VOCAB, B.D_MODEL),
            "final_norm": norm_axes(cfg),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = (B.D_MODEL, B.VOCAB)
        for name, kind, _ in self._stacks():
            p[name] = _with_layer_axis(axes_by_kind[kind](cfg))
        if cfg.arch_type == "hybrid":
            p["shared_attn"] = dense_block_axes(cfg)
        if cfg.mtp:
            p["mtp"] = {
                "proj": (B.D_MODEL, B.D_MODEL),
                "block": dense_block_axes(cfg),
                "norm": norm_axes(cfg),
            }
        return p

    # -- forward -----------------------------------------------------------
    def _scan_stack(self, stack_params, kind, x, positions, mesh_ctx, storage_axes,
                    n_layers, shared_attn=None, force_group=None):
        """Scan over layer groups of size cfg.scan_block_size (FSDP unit)."""
        cfg = self.cfg

        def body(carry, lp):
            x, aux = carry
            x, a = apply_block(cfg, kind, lp, x, positions, mesh_ctx, storage_axes)
            return (x, aux + a)

        def tail(carry):
            x, aux = carry
            x, _ = apply_block(
                cfg, "dense_block", shared_attn, x, positions, mesh_ctx
            )
            return (x, aux)

        stack = ST.Stacked(
            body, n_layers,
            block_size=force_group or cfg.scan_block_size,
            remat=cfg.remat,
            tail=tail if shared_attn is not None else None,
        )
        x, aux = stack.fold(stack_params, (x, jnp.zeros((), jnp.float32)))
        return x, aux

    def backbone(self, params, x, positions, mesh_ctx, storage_axes=()):
        cfg = self.cfg
        if mesh_ctx is not None and mesh_ctx.pp > 1 and mesh_ctx.pipe_axis:
            if cfg.arch_type == "hybrid":
                raise ValueError(
                    "pipeline parallelism does not compose with the "
                    "weight-shared hybrid stack; use an unpipelined plan")
            return self._backbone_pipelined(params, x, positions, mesh_ctx,
                                            storage_axes)
        aux_total = jnp.zeros((), jnp.float32)
        if cfg.arch_type == "hybrid":
            # scan segments: (attn_every - 1) ssm layers + weight-shared attn
            seg = cfg.attn_every - 1
            n_ssm = len([k for k in self.kinds if k == "ssm"])
            x, aux = self._scan_stack(
                params["ssm_blocks"], "ssm", x, positions, mesh_ctx, storage_axes,
                n_ssm, shared_attn=params["shared_attn"], force_group=seg,
            )
            return x, aux
        for name, kind, idxs in self._stacks():
            x, aux = self._scan_stack(
                params[name], kind, x, positions, mesh_ctx, storage_axes, len(idxs)
            )
            aux_total = aux_total + aux
        return x, aux_total

    def _backbone_pipelined(self, params, x, positions, mesh_ctx,
                            storage_axes=()):
        """GPipe the backbone: each stack's ``[L, ...]`` params are viewed
        as ``[S, L/S, ...]`` stages (the staged view is the stored pipe-
        sharded layout, so the reshape is device-local), the batch is split
        into M microbatches, and each stage body is exactly the existing
        :class:`Stacked` fold over its local layers — remat and
        ``scan_block_size`` compose unchanged. Per-layer compute is
        batch-elementwise, so the result is mathematically identical to
        the sequential backbone; the schedule only changes the order.
        Aux losses (router balance) ride the pipeline carry per microbatch.
        Heterogeneous stacks (dense prelude + MoE) are pipelined one after
        another, preserving sequential layer order."""
        from ..sharding import pipeline as PIPE

        cfg = self.cfg
        n_stages = mesh_ctx.pp
        stacks = self._stacks()
        for name, _, idxs in stacks:
            if len(idxs) % n_stages:
                raise ValueError(
                    f"stack {name!r} has {len(idxs)} layers — not divisible "
                    f"into pp={n_stages} stages")
        bsz = x.shape[0]
        n_micro = PIPE.effective_n_micro(mesh_ctx.n_micro, n_stages, bsz)
        carry = {
            "x": PIPE.microbatch(x, n_micro),
            "aux": jnp.zeros((n_micro,), jnp.float32),
        }
        for name, kind, idxs in stacks:
            staged = PIPE.stage_split(params[name], n_stages)
            per_stage = len(idxs) // n_stages

            def stage_fn(sp, c, kind=kind, per_stage=per_stage):
                def body(cr, lp):
                    xx, aux = cr
                    xx, a = apply_block(cfg, kind, lp, xx, positions,
                                        mesh_ctx, storage_axes)
                    return (xx, aux + a)

                stack = ST.Stacked(body, per_stage,
                                   block_size=cfg.scan_block_size,
                                   remat=cfg.remat)
                xx, aux = stack.fold(sp, (c["x"], c["aux"]))
                return {"x": xx, "aux": aux}

            carry = PIPE.pipeline_apply(
                stage_fn, staged, carry, mesh_ctx.mesh,
                pipe_axis=mesh_ctx.pipe_axis, dp_axes=mesh_ctx.dp_axes)
        return PIPE.unmicrobatch(carry["x"]), jnp.sum(carry["aux"])

    def logits(self, params, x, mesh_ctx=None):
        cfg = self.cfg
        with scope(HEAD):
            x = apply_norm(cfg, params["final_norm"], x)
            w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
            out = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
            if mesh_ctx is not None and mesh_ctx.tp_axis is not None:
                out = B.constrain(out, mesh_ctx, None, mesh_ctx.tp_axis)
        return out

    def embed_tokens(self, params, tokens, dtype=jnp.bfloat16):
        with scope(EMBED):
            return params["embed"].astype(dtype)[tokens]

    def apply(self, params, batch, mesh_ctx=None, storage_axes=()):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self.embed_tokens(params, tokens)
        if cfg.n_patches and "patch_embeds" in batch:
            x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
        x = B.constrain(x, mesh_ctx)
        positions = jnp.arange(x.shape[1])
        x, aux = self.backbone(params, x, positions, mesh_ctx, storage_axes)
        logits = self.logits(params, x, mesh_ctx)
        aux_d = {"router_lb": aux}
        if cfg.mtp and "labels" in batch:
            aux_d["mtp"] = self._mtp_loss(params, x, batch, positions, mesh_ctx)
        return logits, aux_d

    def _mtp_loss(self, params, h, batch, positions, mesh_ctx):
        """DeepSeek-V3 MTP: depth-1 next-next-token prediction head."""
        cfg = self.cfg
        mp = params["mtp"]
        emb_next = self.embed_tokens(params, batch["labels"])  # token t+1 embeds
        z = jnp.concatenate([apply_norm(cfg, mp["norm"], h), emb_next], axis=-1)
        z = jnp.einsum("bse,ed->bsd", z, mp["proj"].astype(h.dtype))
        z, _ = apply_block(cfg, "dense_block", mp["block"], z, positions, mesh_ctx)
        logits2 = self.logits(params, z, mesh_ctx)  # predicts token t+2
        labels2 = jnp.roll(batch["labels"], -1, axis=1)
        mask = jnp.ones_like(labels2, jnp.float32).at[:, -1].set(0.0)
        if "loss_mask" in batch:
            mask = mask * batch["loss_mask"]
        return softmax_cross_entropy(logits2, labels2, mask)

    # -- serving -----------------------------------------------------------
    def prefill(self, params, batch, max_len=None, cache_dtype=jnp.bfloat16,
                mesh_ctx=None, storage_axes=()):
        """Run the full prompt, returning (last-token logits, decode cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self.embed_tokens(params, tokens)
        if cfg.n_patches and "patch_embeds" in batch:
            x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
        x = B.constrain(x, mesh_ctx)
        S = x.shape[1]
        max_len = max_len or S
        positions = jnp.arange(S)
        cache: Dict[str, Any] = {}
        if cfg.arch_type == "hybrid":
            x, cache = self._prefill_hybrid(params, x, positions, max_len,
                                            cache_dtype)
        else:
            for name, kind, idxs in self._stacks():

                def body(x, lp, kind=kind):
                    x, c = prefill_block(cfg, kind, lp, x, positions, max_len,
                                         cache_dtype, mesh_ctx, storage_axes)
                    return x, c

                x, cs = ST.Stacked(body, len(idxs)).scan(params[name], x)
                cache[name] = cs
        logits = self.logits(params, x[:, -1:], mesh_ctx)[:, 0]
        return logits, cache

    def prefill_into(self, params, batch, cache, slot, max_len=None,
                     cache_dtype=jnp.bfloat16, mesh_ctx=None, storage_axes=()):
        """Prefill one batch=1 request directly into slot ``slot`` of an
        existing slot-pool cache (``init_cache(n_slots, max_len)`` layout).

        Returns ``(last-token logits [1, vocab], updated pool cache)`` — the
        continuous-batching admission path: jit it with the pool donated and
        ``slot`` traced, and one compile per prompt length serves every slot.
        """
        logits, req_cache = self.prefill(params, batch, max_len=max_len,
                                         cache_dtype=cache_dtype,
                                         mesh_ctx=mesh_ctx,
                                         storage_axes=storage_axes)
        return logits, self.insert_cache(cache, req_cache, slot)

    def _prefill_hybrid(self, params, x, positions, max_len, cache_dtype):
        cfg = self.cfg
        seg = cfg.attn_every - 1
        n_ssm = len([k for k in self.kinds if k == "ssm"])
        nseg = n_ssm // seg
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((nseg, seg) + a.shape[1:]), params["ssm_blocks"]
        )

        def body(x, sp):
            scs = []
            for i in range(seg):
                x, c = prefill_block(cfg, "ssm", _take_layer(sp, i), x, positions,
                                     max_len, cache_dtype)
                scs.append(c)
            x, ac = prefill_block(cfg, "dense_block", params["shared_attn"],
                                  x, positions, max_len, cache_dtype)
            stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *scs)
            return x, (stacked, ac)

        x, (ssm_c, attn_c) = jax.lax.scan(body, x, grouped)
        cache = {
            "ssm_blocks": jax.tree_util.tree_map(
                lambda a: a.reshape((n_ssm,) + a.shape[2:]), ssm_c
            ),
            "shared_attn": attn_c,
        }
        return x, cache

    def init_cache(self, batch, max_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        cache: Dict[str, Any] = {}
        for name, kind, idxs in self._stacks():
            one = init_cache_block(cfg, kind, batch, max_len, dtype)
            cache[name] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (len(idxs),) + a.shape), one
            )
        if cfg.arch_type == "hybrid":
            n_attn = len([k for k in self.kinds if k == "attn_block"])
            one = A.gqa_init_cache(cfg, batch, max_len, dtype)
            cache["shared_attn"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (n_attn,) + a.shape), one
            )
        return cache

    def supports_paged_cache(self) -> bool:
        """Paged serving needs every decode layer to be full-context
        attention over an append-only KV stream: sliding windows re-use
        ring positions (a page would need rewriting after sharing) and SSM
        state is a dense recurrence with no token axis to page."""
        cfg = self.cfg
        return (cfg.arch_type in ("dense", "moe") and cfg.window == 0
                and not cfg.n_patches)

    def init_paged_cache(self, n_blocks, block_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        if not self.supports_paged_cache():
            raise NotImplementedError(
                f"{cfg.name}: paged KV cache needs full-context attention "
                f"layers (arch {cfg.arch_type}, window {cfg.window})")
        cache: Dict[str, Any] = {}
        for name, kind, idxs in self._stacks():
            one = (A.mla_init_paged_cache if cfg.mla
                   else A.gqa_init_paged_cache)(cfg, n_blocks, block_len,
                                                dtype)
            cache[name] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (len(idxs),) + a.shape), one
            )
        return cache

    def _paged_layers(self, params, cache, x, block):
        """The paged layer loop of ``prefill_chunk`` and the paged
        ``decode_step``: per stack, a scan over (layer params, layer index)
        that carries ``(x, pool)``, the pool whole at ``[L, n_blocks, bl,
        ...]``.  ``block(kind, lp, pool, i, x) -> (x, pool)`` writes and
        reads layer ``i`` of the pool.  Carried, not passed as scan inputs
        and outputs, the donated pool is updated in place instead of being
        sliced per layer and restacked into a new buffer."""
        new_cache: Dict[str, Any] = {}
        for name, kind, idxs in self._stacks():

            def body(carry, inp, kind=kind):
                lp, i = inp
                return block(kind, lp, carry[1], i, carry[0]), None

            (x, new_cache[name]), _ = jax.lax.scan(
                body, (x, cache[name]), (params[name], jnp.arange(len(idxs))))
        return x, new_cache

    def prefill_chunk(self, params, cache, pages_row, tokens, start, n_valid,
                      mesh_ctx=None, storage_axes=()):
        """Run one fixed-shape prompt chunk into a request's pages.

        ``tokens`` i32 [C] (entries past ``n_valid`` are padding, zeroed by
        the caller), ``start`` the absolute position of ``tokens[0]``,
        ``pages_row`` i32 [max_pages] this request's physical block ids.
        Returns (logits of the last valid row [1, vocab], new cache) — the
        logits only matter on the final chunk of an admission.
        """
        cfg = self.cfg
        x = self.embed_tokens(params, tokens[None])
        x = B.constrain(x, mesh_ctx)
        positions = start + jnp.arange(tokens.shape[0])

        def block(kind, lp, pool, i, x):
            return prefill_chunk_block(cfg, kind, lp, pool, i, x, positions,
                                       pages_row, n_valid, mesh_ctx,
                                       storage_axes)

        x, new_cache = self._paged_layers(params, cache, x, block)
        last = jnp.take(x, n_valid - 1, axis=1)          # [1, D]
        logits = self.logits(params, last[:, None], mesh_ctx)[:, 0]
        return logits, new_cache

    def decode_step(self, params, cache, tokens, positions, mesh_ctx=None,
                    pages=None, active=None):
        cfg = self.cfg
        x = self.embed_tokens(params, tokens[:, None])
        new_cache: Dict[str, Any] = {}
        if pages is not None:

            def block(kind, lp, pool, i, x):
                return decode_block_paged(cfg, kind, lp, pool, i, x,
                                          positions, pages, active, mesh_ctx)

            x, new_cache = self._paged_layers(params, cache, x, block)
        elif cfg.arch_type == "hybrid":
            x, new_cache = self._decode_hybrid(params, cache, x, positions)
        else:
            for name, kind, idxs in self._stacks():

                def body(x, inp, kind=kind):
                    lp, lc = inp
                    x, nc, _ = decode_block(cfg, kind, lp, lc, x, positions,
                                            mesh_ctx)
                    return x, nc

                x, nc = ST.Stacked(body, len(idxs)).scan(
                    (params[name], cache[name]), x)
                new_cache[name] = nc
        logits = self.logits(params, x, mesh_ctx)[:, 0]
        return logits, new_cache

    def _decode_hybrid(self, params, cache, x, positions):
        cfg = self.cfg
        seg = cfg.attn_every - 1
        n_ssm = len([k for k in self.kinds if k == "ssm"])
        nseg = n_ssm // seg
        ssm_p = jax.tree_util.tree_map(
            lambda a: a.reshape((nseg, seg) + a.shape[1:]), params["ssm_blocks"]
        )
        ssm_c = jax.tree_util.tree_map(
            lambda a: a.reshape((nseg, seg) + a.shape[1:]), cache["ssm_blocks"]
        )

        def body(x, inp):
            sp, sc, ac = inp
            ncs = []
            for i in range(seg):
                x, nc, _ = decode_block(cfg, "ssm", _take_layer(sp, i),
                                        _take_layer(sc, i), x, positions)
                ncs.append(nc)
            x, nac, _ = decode_block(cfg, "dense_block", params["shared_attn"],
                                     ac, x, positions)
            stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ncs)
            return x, (stacked, nac)

        x, (new_ssm, new_attn) = jax.lax.scan(
            body, x, (ssm_p, ssm_c, cache["shared_attn"])
        )
        new_cache = {
            "ssm_blocks": jax.tree_util.tree_map(
                lambda a: a.reshape((n_ssm,) + a.shape[2:]), new_ssm
            ),
            "shared_attn": new_attn,
        }
        return x, new_cache
