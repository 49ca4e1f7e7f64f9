"""Attention: GQA/MQA (+bias, sliding window), MLA, train/prefill/decode paths.

Sharding-agnostic: everything is einsum/scan over named-logical-axis params;
pjit + NamedSharding decide the distribution. Long sequences (> _BLOCKWISE_AT)
use a blockwise online-softmax scan so no [S, S] score tensor is ever live —
this is also the pure-jnp oracle for the Pallas flash kernel.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import base as B
from .common import apply_rope, dense_init, rmsnorm
from ..telemetry.spans import KV_READ, KV_WRITE, scope

_BLOCKWISE_AT = 4096     # use blockwise path for S strictly above this
# (<=4k trains through the plain einsum path — differentiable without
#  stacking per-block softmax residuals; >4k is inference-prefill where the
#  online-softmax scan runs forward-only. On real TPU the Pallas flash
#  kernel with its recompute-vjp covers the training case.)
_KV_BLOCK = 1024
_MLA_KV_BLOCK = 512

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA params
# ---------------------------------------------------------------------------
def init_gqa(cfg: B.ArchConfig, rng) -> Dict[str, Any]:
    D, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    rq, rk, rv, ro = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(rq, (D, H, dh), D),
        "wk": dense_init(rk, (D, K, dh), D),
        "wv": dense_init(rv, (D, K, dh), D),
        "wo": dense_init(ro, (H, dh, D), H * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, dh), jnp.float32)
        p["bk"] = jnp.zeros((K, dh), jnp.float32)
        p["bv"] = jnp.zeros((K, dh), jnp.float32)
    return p


def gqa_axes(cfg: B.ArchConfig) -> Dict[str, Any]:
    p = {
        "wq": (B.D_MODEL, B.HEADS, B.HEAD_DIM),
        "wk": (B.D_MODEL, B.KV_HEADS, B.HEAD_DIM),
        "wv": (B.D_MODEL, B.KV_HEADS, B.HEAD_DIM),
        "wo": (B.HEADS, B.HEAD_DIM, B.D_MODEL),
    }
    if cfg.qkv_bias:
        p["bq"] = (B.HEADS, B.HEAD_DIM)
        p["bk"] = (B.KV_HEADS, B.HEAD_DIM)
        p["bv"] = (B.KV_HEADS, B.HEAD_DIM)
    return p


def _project_qkv(p, x, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def _gqa_scores_einsum(q, k):
    """q [B,S,H,dh], k [B,T,K,dh] -> scores [B,H,S,T] (grouped heads)."""
    Bq, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(Bq, S, K, G, dh)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k)
    return s.reshape(Bq, K * G, S, k.shape[1])


def _gqa_out_einsum(probs, v):
    """probs [B,H,S,T], v [B,T,K,dh] -> [B,S,H,dh]."""
    Bq, H, S, T = probs.shape
    K = v.shape[2]
    G = H // K
    pg = probs.reshape(Bq, K, G, S, T)
    o = jnp.einsum("bkgst,btkd->bskgd", pg, v)
    return o.reshape(Bq, S, H, v.shape[3])


def _full_attn(q, k, v, positions_q, positions_k, window: int, causal: bool):
    """Plain path; scores materialized. q [B,S,H,dh] k/v [B,T,K,dh]."""
    dh = q.shape[-1]
    scores = _gqa_scores_einsum(q, k).astype(jnp.float32) / math.sqrt(dh)
    mask = jnp.ones(scores.shape[-2:], bool)
    rel = positions_q[:, None] - positions_k[None, :]  # [S, T]
    if causal:
        mask = mask & (rel >= 0)
    if window > 0:
        mask = mask & (rel < window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_out_einsum(probs, v)


def _blockwise_attn(q, k, v, positions_q, positions_k, window: int, causal: bool,
                    kv_block: int = _KV_BLOCK):
    """Online-softmax over KV blocks; never materializes [S, T]."""
    Bq, S, H, dh = q.shape
    T = k.shape[1]
    K = k.shape[2]
    G = H // K
    nblk = -(-T // kv_block)
    pad = nblk * kv_block - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions_k = jnp.pad(positions_k, (0, pad), constant_values=-(10 ** 9))
    kb = k.reshape(Bq, nblk, kv_block, K, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(Bq, nblk, kv_block, K, dh).transpose(1, 0, 2, 3, 4)
    pb = positions_k.reshape(nblk, kv_block)
    qg = (q.reshape(Bq, S, K, G, dh) / math.sqrt(dh)).astype(jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, pblk = blk
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kblk.astype(jnp.float32))
        rel = positions_q[:, None] - pblk[None, :]
        mask = jnp.ones_like(rel, dtype=bool)
        if causal:
            mask = mask & (rel >= 0)
        if window > 0:
            mask = mask & (rel < window)
        mask = mask & (pblk >= 0)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((Bq, K, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, K, G, S), jnp.float32)
    a0 = jnp.zeros((Bq, K, G, S, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(Bq, S, H, dh)
    return out.astype(q.dtype)


def gqa_forward(cfg: B.ArchConfig, p, x, positions, window: Optional[int] = None,
                return_kv: bool = False):
    """Training/prefill self-attention. x [B,S,D]; positions [S]."""
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    w = cfg.window if window is None else window
    S = x.shape[1]
    if cfg.use_flash_kernel:
        from ..kernels.flash.ops import flash_attention

        o = flash_attention(q, k, v, causal=True, window=w,
                            block_q=min(128, S), block_kv=min(128, S))
    elif S > _BLOCKWISE_AT:
        o = _blockwise_attn(q, k, v, positions, positions, w, causal=True)
    else:
        o = _full_attn(q, k, v, positions, positions, w, causal=True)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


def bidir_forward(cfg: B.ArchConfig, p, x):
    """Bidirectional (encoder) self-attention, no rope (whisper uses learned pos)."""
    q, k, v = _project_qkv(p, x, cfg)
    pos = jnp.arange(x.shape[1])
    o = _full_attn(q, k, v, pos, pos, window=0, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def cross_forward(cfg: B.ArchConfig, p, x, enc_kv):
    """Cross-attention: q from x, k/v precomputed from encoder output."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    k, v = enc_kv
    pos_q = jnp.arange(x.shape[1])
    pos_k = jnp.arange(k.shape[1])
    o = _full_attn(q, k, v, pos_q, pos_k, window=0, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def cross_kv(cfg: B.ArchConfig, p, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(enc_out.dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(enc_out.dtype)
        v = v + p["bv"].astype(enc_out.dtype)
    return k, v


# ---------------------------------------------------------------------------
# GQA decode (single token, cache [B, L, K, dh]; ring buffer when windowed)
# ---------------------------------------------------------------------------
def gqa_init_cache(cfg: B.ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    K, dh = cfg.n_kv_heads, cfg.head_dim_
    L = min(max_len, cfg.window) if cfg.window > 0 else max_len
    return {
        "k": jnp.zeros((batch, L, K, dh), dtype),
        "v": jnp.zeros((batch, L, K, dh), dtype),
    }


def gqa_decode(cfg: B.ArchConfig, p, cache, x, positions):
    """x [B,1,D]; positions [B]; returns (out [B,1,D], new cache)."""
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions[:, None], cfg.rope_theta)
    k = apply_rope(k, positions[:, None], cfg.rope_theta)
    L = cache["k"].shape[1]
    slot = positions % L if cfg.window > 0 else positions
    bidx = jnp.arange(x.shape[0])
    ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))

    dh = q.shape[-1]
    scores = _gqa_scores_einsum(q, ck).astype(jnp.float32) / math.sqrt(dh)  # [B,H,1,L]
    n_valid = jnp.minimum(positions + 1, L)                                  # [B]
    valid = jnp.arange(L)[None, :] < n_valid[:, None]                        # [B,L]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_out_einsum(probs, cv)                                           # [B,1,H,dh]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (deepseek-v3): latent KV compression
# ---------------------------------------------------------------------------
def init_mla(cfg: B.ArchConfig, rng) -> Dict[str, Any]:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    r = jax.random.split(rng, 5)
    return {
        "wq_a": dense_init(r[0], (D, m.q_lora), D),
        "q_norm": jnp.ones((m.q_lora,), jnp.float32),
        "wq_b": dense_init(r[1], (m.q_lora, H, m.head_dim_nope + m.head_dim_rope), m.q_lora),
        "wkv_a": dense_init(r[2], (D, m.kv_lora + m.head_dim_rope), D),
        "kv_norm": jnp.ones((m.kv_lora,), jnp.float32),
        "wkv_b": dense_init(r[3], (m.kv_lora, H, m.head_dim_nope + m.head_dim_v), m.kv_lora),
        "wo": dense_init(r[4], (H, m.head_dim_v, D), H * m.head_dim_v),
    }


def mla_axes(cfg: B.ArchConfig) -> Dict[str, Any]:
    return {
        "wq_a": (B.D_MODEL, B.LORA),
        "q_norm": (B.LORA,),
        "wq_b": (B.LORA, B.HEADS, B.HEAD_DIM),
        "wkv_a": (B.D_MODEL, B.LORA),
        "kv_norm": (B.LORA,),
        "wkv_b": (B.LORA, B.HEADS, B.HEAD_DIM),
        "wo": (B.HEADS, B.HEAD_DIM, B.D_MODEL),
    }


def _mla_qkv(cfg, p, x, positions):
    m = cfg.mla
    cq = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype))
    cq = rmsnorm(cq, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(x.dtype))
    q_nope, q_rope = jnp.split(q, [m.head_dim_nope], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv, k_rope = jnp.split(ckv, [m.kv_lora], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(cfg, p, c_kv):
    m = cfg.mla
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(c_kv.dtype))
    return jnp.split(kv, [m.head_dim_nope], axis=-1)  # k_nope, v


def mla_forward(cfg: B.ArchConfig, p, x, positions, return_latent: bool = False):
    """Training/prefill MLA self-attention (blockwise over KV for long S)."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, x, positions)
    scale = 1.0 / math.sqrt(m.head_dim_nope + m.head_dim_rope)
    S = x.shape[1]

    if S <= _BLOCKWISE_AT:
        k_nope, v = _mla_expand_kv(cfg, p, c_kv)
        s = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
        s = s + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)
        s = s.astype(jnp.float32) * scale
        rel = positions[:, None] - positions[None, :]
        s = jnp.where((rel >= 0)[None, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhst,bthk->bshk", probs, v)
    else:
        o = _mla_blockwise(cfg, p, q_nope, q_rope, c_kv, k_rope, positions, scale)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    if return_latent:
        return out, (c_kv, k_rope)
    return out


def _mla_blockwise(cfg, p, q_nope, q_rope, c_kv, k_rope, positions, scale,
                   kv_block: int = _MLA_KV_BLOCK):
    """Blockwise MLA: expand latent -> k/v one block at a time."""
    m = cfg.mla
    Bq, S, H, dn = q_nope.shape
    T = c_kv.shape[1]
    nblk = -(-T // kv_block)
    pad = nblk * kv_block - T
    pk = positions
    if pad:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
        pk = jnp.pad(pk, (0, pad), constant_values=-(10 ** 9))
    cb = c_kv.reshape(Bq, nblk, kv_block, -1).transpose(1, 0, 2, 3)
    rb = k_rope.reshape(Bq, nblk, kv_block, -1).transpose(1, 0, 2, 3)
    pb = pk.reshape(nblk, kv_block)
    qn = q_nope.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)

    def step(carry, blk):
        mx, l, acc = carry
        cblk, rblk, pblk = blk
        k_nope, v = _mla_expand_kv(cfg, p, cblk)
        s = jnp.einsum("bshk,bthk->bhst", qn, k_nope.astype(jnp.float32))
        s = s + jnp.einsum("bshk,btk->bhst", qr, rblk.astype(jnp.float32))
        s = s * scale
        rel = positions[:, None] - pblk[None, :]
        mask = (rel >= 0) & (pblk >= 0)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(mx, jnp.max(s, axis=-1))
        pr = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mx - m_new)
        l = l * corr + jnp.sum(pr, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhst,bthk->bhsk", pr, v.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((Bq, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq, H, S), jnp.float32)
    a0 = jnp.zeros((Bq, H, S, m.head_dim_v), jnp.float32)
    (mx, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (cb, rb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q_nope.dtype)  # [B,S,H,dv]


def mla_init_cache(cfg: B.ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.head_dim_rope), dtype),
    }


# ---------------------------------------------------------------------------
# Paged KV cache (serving): [L, n_blocks, block_len, ...] leaves + page tables
# ---------------------------------------------------------------------------
# The serve engine's block allocator hands each request a row of physical
# block ids; attention reads the cache *through* that row (gather) and
# writes the current token's K/V into (block, offset) = (row[pos // bl],
# pos % bl) (scatter).  Each primitive takes the whole full-depth leaf and
# its layer index: the layer loop carries the pool, so a layer's write is
# one in-place scatter into the carried (donated) buffer and its read one
# gather of just the pages in the rows — no per-layer slice is materialised.
# Two JAX indexing facts are load-bearing here, on axis 1 (the block axis):
#
# - gathers CLAMP out-of-bounds indices and WRAP negative ones, so a page
#   table's -1 (unallocated) entries resolve to real-but-wrong pages whose
#   values are finite garbage — always behind the causal/validity mask, so
#   softmax gives them exactly-0 probability and they never reach the output;
# - scatters DROP positive out-of-bounds indices, so suppressed writes
#   (inactive slots, padding rows of a prefill chunk) use the sentinel
#   ``n_blocks``.  -1 would WRAP and corrupt the last live block.


def paged_view(leaf, layer, pages):
    """Gather layer ``layer`` of ``leaf [L, n_blocks, bl, ...]`` through
    ``pages [..., n_pages]`` into a contiguous view ``[..., n_pages * bl,
    ...]``."""
    with scope(KV_READ):
        v = leaf[layer, pages]
        lead = pages.shape[:-1]
        return v.reshape(lead + (pages.shape[-1] * leaf.shape[2],)
                         + leaf.shape[3:])


def _paged_write(leaf, layer, phys, off, vals):
    """Scatter ``vals [N, ...]`` rows, flattened to the leaf's feature
    shape, into ``leaf[layer, phys[i], off[i]]`` (``phys == n_blocks``
    drops the write)."""
    with scope(KV_WRITE):
        vals = vals.reshape(vals.shape[:1] + leaf.shape[3:])
        return leaf.at[layer, phys, off].set(vals.astype(leaf.dtype))


def gqa_init_paged_cache(cfg: B.ArchConfig, n_blocks: int, block_len: int,
                         dtype=jnp.bfloat16):
    """K/V pages of ``block_len`` rows of ``K * dh`` features, heads-major.

    One feature axis, not ``[K, dh]``: with a head size under 128 lanes the
    TPU's default layout of a ``[.., bl, K, dh]`` leaf puts the block axis
    minor-most, and the compiler then copies the whole pool into and out of
    a row-major layout around the layer loop for the page gathers."""
    K, dh = cfg.n_kv_heads, cfg.head_dim_
    return {
        "k": jnp.zeros((n_blocks, block_len, K * dh), dtype),
        "v": jnp.zeros((n_blocks, block_len, K * dh), dtype),
    }


def _gqa_paged_kv(ck, cv, layer, pages, heads):
    """K and V views through ``pages [B, n_pages]``, features split back
    into ``heads`` = (K, dh): ``[B, n_pages * bl, K, dh]``."""
    shape = pages.shape[:1] + (-1,) + tuple(heads)
    return (paged_view(ck, layer, pages).reshape(shape),
            paged_view(cv, layer, pages).reshape(shape))


def gqa_decode_paged(cfg: B.ArchConfig, p, cache, layer, x, positions, pages,
                     active=None):
    """Single-token GQA decode through page tables.

    ``cache`` holds the full-depth pool, ``layer`` this layer's index in it;
    x [B,1,D]; positions [B]; pages int32 [B, n_pages] physical block ids
    per slot; active bool [B] suppresses cache writes for dead slots (their
    frozen positions may alias pages since freed and reused)."""
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions[:, None], cfg.rope_theta)
    k = apply_rope(k, positions[:, None], cfg.rope_theta)
    nb, bl = cache["k"].shape[1:3]
    phys = jnp.take_along_axis(pages, (positions // bl)[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, nb)
    ck = _paged_write(cache["k"], layer, phys, positions % bl, k[:, 0])
    cv = _paged_write(cache["v"], layer, phys, positions % bl, v[:, 0])
    vk, vv = _gqa_paged_kv(ck, cv, layer, pages, k.shape[2:])    # [B,T,K,dh]
    dh = q.shape[-1]
    scores = _gqa_scores_einsum(q, vk).astype(jnp.float32) / math.sqrt(dh)
    T = vk.shape[1]
    valid = jnp.arange(T)[None, :] < (positions + 1)[:, None]    # [B,T]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_out_einsum(probs, vv.astype(x.dtype))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"k": ck, "v": cv}


def gqa_prefill_chunk(cfg: B.ArchConfig, p, cache, layer, x, positions,
                      pages_row, n_valid):
    """One fixed-shape prefill chunk: C prompt rows into one request's pages.

    ``cache``/``layer`` as in ``gqa_decode_paged``; x [1,C,D]; positions
    [C] absolute; pages_row int32 [n_pages]; rows at index >= n_valid are
    padding (writes dropped, outputs garbage).  The chunk shape never
    depends on the prompt length, so a page's stored K/V is bitwise
    identical whether the prompt was short or long, cold or a cache hit —
    the canonical-page property the radix index shares under.
    """
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    nb, bl = cache["k"].shape[1:3]
    row_idx = jnp.arange(positions.shape[0])
    phys = jnp.where(row_idx < n_valid, pages_row[positions // bl], nb)
    ck = _paged_write(cache["k"], layer, phys, positions % bl, k[0])
    cv = _paged_write(cache["v"], layer, phys, positions % bl, v[0])
    vk, vv = _gqa_paged_kv(ck, cv, layer, pages_row[None], k.shape[2:])
    dh = q.shape[-1]
    scores = _gqa_scores_einsum(q, vk).astype(jnp.float32) / math.sqrt(dh)
    T = vk.shape[1]
    valid = positions[:, None] >= jnp.arange(T)[None, :]         # [C,T] causal
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_out_einsum(probs, vv.astype(x.dtype))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"k": ck, "v": cv}


def mla_init_paged_cache(cfg: B.ArchConfig, n_blocks: int, block_len: int,
                         dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((n_blocks, block_len, m.kv_lora), dtype),
        "k_rope": jnp.zeros((n_blocks, block_len, m.head_dim_rope), dtype),
    }


def mla_decode_paged(cfg: B.ArchConfig, p, cache, layer, x, positions, pages,
                     active=None, absorb: bool = False):
    """Single-token MLA decode against the paged latent cache (see
    ``gqa_decode_paged``)."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, x, positions[:, None])
    nb, bl = cache["c_kv"].shape[1:3]
    phys = jnp.take_along_axis(pages, (positions // bl)[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, nb)
    cc = _paged_write(cache["c_kv"], layer, phys, positions % bl,
                      c_kv[:, 0])
    cr = _paged_write(cache["k_rope"], layer, phys, positions % bl,
                      k_rope[:, 0])
    vc = paged_view(cc, layer, pages)  # [B,T,r]
    vr = paged_view(cr, layer, pages)
    scale = 1.0 / math.sqrt(m.head_dim_nope + m.head_dim_rope)
    T = vc.shape[1]
    valid = jnp.arange(T)[None, :] <= positions[:, None]

    if absorb:
        wkb = p["wkv_b"].astype(x.dtype)
        wk, wv = jnp.split(wkb, [m.head_dim_nope], axis=-1)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk)
        s = jnp.einsum("bshr,btr->bhst", q_lat, vc)
        s = s + jnp.einsum("bshk,btk->bhst", q_rope, vr)
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, vc)
        o = jnp.einsum("bshr,rhk->bshk", o_lat, wv)
    else:
        k_nope, v = _mla_expand_kv(cfg, p, vc.astype(x.dtype))
        s = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
        s = s + jnp.einsum("bshk,btk->bhst", q_rope, vr.astype(x.dtype))
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhst,bthk->bshk", probs, v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"c_kv": cc, "k_rope": cr}


def mla_prefill_chunk(cfg: B.ArchConfig, p, cache, layer, x, positions,
                      pages_row, n_valid):
    """One fixed-shape MLA prefill chunk (see ``gqa_prefill_chunk``)."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, x, positions)
    nb, bl = cache["c_kv"].shape[1:3]
    row_idx = jnp.arange(positions.shape[0])
    phys = jnp.where(row_idx < n_valid, pages_row[positions // bl], nb)
    cc = _paged_write(cache["c_kv"], layer, phys, positions % bl, c_kv[0])
    cr = _paged_write(cache["k_rope"], layer, phys, positions % bl, k_rope[0])
    vc = paged_view(cc, layer, pages_row[None])  # [1,T,r]
    vr = paged_view(cr, layer, pages_row[None])
    scale = 1.0 / math.sqrt(m.head_dim_nope + m.head_dim_rope)
    T = vc.shape[1]
    k_nope, v = _mla_expand_kv(cfg, p, vc.astype(x.dtype))
    s = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
    s = s + jnp.einsum("bshk,btk->bhst", q_rope, vr.astype(x.dtype))
    s = s.astype(jnp.float32) * scale
    valid = positions[:, None] >= jnp.arange(T)[None, :]         # [C,T]
    s = jnp.where(valid[None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhst,bthk->bshk", probs, v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"c_kv": cc, "k_rope": cr}


def mla_decode(cfg: B.ArchConfig, p, cache, x, positions, absorb: bool = False):
    """Single-token MLA decode against the latent cache."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, x, positions[:, None])
    bidx = jnp.arange(x.shape[0])
    cc = cache["c_kv"].at[bidx, positions].set(c_kv[:, 0].astype(cache["c_kv"].dtype))
    cr = cache["k_rope"].at[bidx, positions].set(k_rope[:, 0].astype(cache["k_rope"].dtype))
    scale = 1.0 / math.sqrt(m.head_dim_nope + m.head_dim_rope)
    L = cc.shape[1]
    valid = jnp.arange(L)[None, :] <= positions[:, None]

    if absorb:
        # fold wkv_b into the query/output sides: score and accumulate in the
        # 512-dim latent space — no per-step K/V expansion.
        wkb = p["wkv_b"].astype(x.dtype)                     # [r, H, dn+dv]
        wk, wv = jnp.split(wkb, [m.head_dim_nope], axis=-1)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk)     # [B,1,H,r]
        s = jnp.einsum("bshr,btr->bhst", q_lat, cc)
        s = s + jnp.einsum("bshk,btk->bhst", q_rope, cr)
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, cc)      # [B,1,H,r]
        o = jnp.einsum("bshr,rhk->bshk", o_lat, wv)          # [B,1,H,dv]
    else:
        k_nope, v = _mla_expand_kv(cfg, p, cc.astype(x.dtype))
        s = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
        s = s + jnp.einsum("bshk,btk->bhst", q_rope, cr.astype(x.dtype))
        s = s.astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhst,bthk->bshk", probs, v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"c_kv": cc, "k_rope": cr}
