"""Jit'd public wrapper: [B, S, H, dh] GQA layout -> flash kernel layout.

Differentiable: forward runs the Pallas kernel; backward recomputes through
the pure-jnp oracle (flash-style recompute vjp — no [S,T] residuals saved).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bkg
from .ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, window, block_q, block_kv):
    return _forward(q, k, v, causal, window, block_q, block_kv)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128):
    """q [B, Sq, H, dh]; k/v [B, Skv, K, dh]; returns [B, Sq, H, dh]."""
    return _flash_vjp(q, k, v, causal, window, block_q, block_kv)


def _forward(q, k, v, causal, window, block_q, block_kv):
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    qf = (
        q.reshape(B, Sq, K, G, dh)
        .transpose(0, 2, 3, 1, 4)
        .reshape(B * K * G, Sq, dh)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, k.shape[1], dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, v.shape[1], dh)
    of = flash_attention_bkg(
        qf, kf, vf, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv,
    )
    return (
        of.reshape(B, K, G, Sq, dh).transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dh)
    )


def _fwd(q, k, v, causal, window, block_q, block_kv):
    out = _forward(q, k, v, causal, window, block_q, block_kv)
    return out, (q, k, v)


def _bwd(causal, window, block_q, block_kv, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: attention_ref(q, k, v, causal=causal, window=window),
        q, k, v,
    )
    return vjp(g)


_flash_vjp.defvjp(_fwd, _bwd)
