"""Operations the algorithm needs, counted from shapes (multiply-add = 2).

Training: 6·N·tokens for every parameter matrix product (N counts a tied
embedding once: its lookup is free and its use as the head is one
product), plus the causal attention scores and the SSD chunk terms, which
no parameter count holds. Recomputation under remat does not count. N is
the parameters a token's products use: a family's ``active_params(c)``
where its reference module defines one (a mixture of experts: k of E
routed experts and everything else), else every parameter.

Serving: per processed token 2·N minus the head, the head once for every
row whose logits are used, and attention over the live context.
"""
from __future__ import annotations

import math


def n_params(ref, c: dict) -> int:
    return sum(math.prod(shape) for shape, _ in ref.param_specs(c).values())


def model_params(ref, c: dict) -> int:
    """The parameters one token's products use (see the module's note)."""
    if hasattr(ref, "active_params"):
        return ref.active_params(c)
    return n_params(ref, c)


def _head_params(d) -> int:
    return d.V * d.D


def attention_fwd(d, S: int) -> float:
    """Causal scores and weighted values of one sequence of ``S``: QK^T and
    PV over S^2/2 query-key pairs, per layer."""
    if not hasattr(d, "H") or not hasattr(d, "dh"):
        return 0.0
    return 2 * 2 * (S * S / 2) * d.H * d.dh * d.L


def ssd_fwd(d, S: int) -> float:
    """The SSD chunked dual form of one sequence: C_i.B_j and the masked
    mixing within each chunk (S^2/2 pairs per chunk, so S.Q/2 per
    sequence), then the state's read-out C.h and its update B.(dt x)."""
    if not hasattr(d, "N"):
        return 0.0
    Q = min(d.Q, S)
    pairs = S * Q / 2
    return d.L * (2 * pairs * d.N * d.G + 2 * pairs * d.H * d.P
                  + 2 * 2 * S * d.H * d.P * d.N)


def train_step(ref, c: dict, batch: int, seq: int) -> float:
    d = ref.dims(c)
    return (6.0 * model_params(ref, c) * batch * seq
            + 3.0 * batch * (attention_fwd(d, seq) + ssd_fwd(d, seq)))


def _attn_positions(d, lo: int, hi: int) -> float:
    """Attention of the tokens at positions lo..hi-1, each over t+1 keys."""
    if hi <= lo or not hasattr(d, "dh"):
        return 0.0
    keys = (hi * (hi + 1) - lo * (lo + 1)) / 2       # sum of (t + 1)
    return 2 * 2 * keys * d.H * d.dh * d.L


def serve_request(ref, c: dict, prompt_len: int, cached: int,
                  n_gen: int) -> float:
    """One served request: prefill of positions ``cached..prompt_len-1``
    with logits for the last, then ``n_gen - 1`` decode steps."""
    d = ref.dims(c)
    embedding = _head_params(d) * (1 if d.tied else 2)   # lookup + head
    body = 2.0 * (model_params(ref, c) - embedding)
    head = 2.0 * _head_params(d)
    prefill = prompt_len - cached
    decode = max(n_gen - 1, 0)
    return ((prefill + decode) * body + (1 + decode) * head
            + _attn_positions(d, cached, prompt_len)
            + _attn_positions(d, prompt_len, prompt_len + decode))
