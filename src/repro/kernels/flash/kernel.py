"""Flash-attention forward Pallas TPU kernel.

Grid (B·K·G, n_q_blocks, n_kv_blocks) with the KV dimension innermost and
sequential; online-softmax statistics (m, l, acc) live in VMEM scratch across
KV iterations. Tiles are MXU-aligned (multiples of 128 on the matmul dims).
GQA is handled in the index maps (K/V tiles indexed by bh // group_size) —
no KV repetition in HBM.

Masks: causal and/or sliding window, plus padding masks for non-multiple
sequence lengths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_call

NEG_INF = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, block_q: int, block_kv: int, n_kv: int,
                causal: bool, window: int, s_q: int, s_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale           # [bq, dh]
    k = k_ref[0].astype(jnp.float32)                   # [bk, dh]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

    pos_q = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    pos_k = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = (pos_q < s_q) & (pos_k < s_kv)
    if causal:
        mask = mask & (pos_q >= pos_k)
    if window > 0:
        mask = mask & (pos_q - pos_k < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ()))
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bkg(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_kv: int = 128):
    """q [BKG, Sq, dh] grouped-flattened queries; k/v [BK, Skv, dh].

    BKG = batch · kv_heads · group_size; BK = batch · kv_heads. K/V tiles are
    shared across the G query heads of each group via the index map.
    """
    BKG, Sq, dh = q.shape
    BK, Skv, _ = k.shape
    G = BKG // BK
    scale = 1.0 / math.sqrt(dh)
    nq = -(-Sq // block_q)
    nk = -(-Skv // block_kv)
    pad_q = nq * block_q - Sq
    pad_k = nk * block_kv - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _flash_body, scale=scale, block_q=block_q, block_kv=block_kv,
        n_kv=nk, causal=causal, window=window, s_q=Sq, s_kv=Skv,
    )
    out = pallas_call(
        kernel,
        grid=(BKG, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_kv, dh), lambda bh, iq, ik, G=G: (bh // G, ik, 0)),
            pl.BlockSpec((1, block_kv, dh), lambda bh, iq, ik, G=G: (bh // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BKG, nq * block_q, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
    )(q, k, v)
    return out[:, :Sq]
