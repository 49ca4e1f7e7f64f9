"""Plain float32 reference of a dense decoder with QKV bias and a tied head
(the Qwen2 / Qwen1.5 architecture, hf:Qwen/Qwen1.5-0.5B, ``model_type``
"qwen2").

Per layer: RMSNorm -> causal self-attention with bias on q, k and v, rotary
embeddings on q and k (rotate-half form, ``rope_theta``), grouped K/V heads
-> residual -> RMSNorm -> SiLU-gated MLP -> residual. A final RMSNorm, then
logits against the embedding matrix (``tie_word_embeddings``).

Departures from the published description: none in the mathematics.
Attention is computed with materialised scores over the whole sequence (no
sliding window: ``use_sliding_window`` is false in the published config),
and dropout is absent (``attention_dropout`` 0).

Parameters are a flat dict of float32 arrays; per-layer parameters are
stacked on a leading layer axis and named ``layers/<name>``.
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from .numerics import cross_entropy_sum, mm, rmsnorm

#: The harness's own tests at CPU size (``bench/tests/tiny.py``): the
#: published keys cut to a few dozen, and each job's limits at that size,
#: set between the program's readings and the control's on seeds 1-4
#: (``bench/calibrate.py`` on the CPU): serving, program <= 0.19 and
#: control >= 0.70; training, loss 0.0046 / 0.0147 and gradient 0.023 /
#: 0.023. The update norm does not separate the control at this size
#: (0.0135 / 0.010); its limit catches a state left unchanged, which reads
#: 1. The weights are ten times the published scale: at this width the
#: served tokens then depend on their context, not only on the token
#: before them.
TINY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 2, "num_hidden_layers": 2,
               "intermediate_size": 128, "vocab_size": 512,
               "initializer_range": 0.2},
    "limits": {"train": {"loss_gap": 0.009, "grad_norm_gap": 0.05,
                         "update_norm_gap": 0.5},
               "serve": {"logit_gap": 0.4}},
}


def dims(c: dict) -> SimpleNamespace:
    H = c["num_attention_heads"]
    return SimpleNamespace(
        L=c["num_hidden_layers"], D=c["hidden_size"], H=H,
        K=c["num_key_value_heads"], dh=c["hidden_size"] // H,
        F=c["intermediate_size"], V=c["vocab_size"],
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]))


def param_specs(c: dict) -> dict:
    """name -> (shape, init); init as the weight generator reads it."""
    d = dims(c)
    L, D, H, K, dh, F, V = d.L, d.D, d.H, d.K, d.dh, d.F, d.V
    std = float(c.get("initializer_range", 0.02))
    normal = ("normal", std)
    norm = ("around_one", std)
    specs = {
        "embed": ((V, D), normal),
        "final_norm": ((D,), norm),
        "layers/attn_norm": ((L, D), norm),
        "layers/wq": ((L, D, H, dh), normal),
        "layers/wk": ((L, D, K, dh), normal),
        "layers/wv": ((L, D, K, dh), normal),
        "layers/bq": ((L, H, dh), normal),
        "layers/bk": ((L, K, dh), normal),
        "layers/bv": ((L, K, dh), normal),
        "layers/wo": ((L, H, dh, D), normal),
        "layers/mlp_norm": ((L, D), norm),
        "layers/w_gate": ((L, D, F), normal),
        "layers/w_up": ((L, D, F), normal),
        "layers/w_down": ((L, F, D), normal),
    }
    if not d.tied:
        specs["lm_head"] = ((D, V), normal)
    return specs


def _rope(x, pos, theta):
    """x [B, T, h, dh] at absolute positions ``pos [T]``; rotate-half."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]          # [T, dh/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rot * sin


def _layer(d, mode, x, lw):
    T = x.shape[1]
    pos = jnp.arange(T)
    h = rmsnorm(x, lw["attn_norm"], d.eps)
    q = mm("btd,dhk->bthk", h, lw["wq"], mode) + lw["bq"]
    k = mm("btd,dhk->bthk", h, lw["wk"], mode) + lw["bk"]
    v = mm("btd,dhk->bthk", h, lw["wv"], mode) + lw["bv"]
    q, k = _rope(q, pos, d.theta), _rope(k, pos, d.theta)
    rep = d.H // d.K
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = mm("bqhk,bthk->bhqt", q, k, mode) / math.sqrt(d.dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhqt,bthk->bqhk", p, v, mode)
    x = x + mm("bqhk,hkd->bqd", o, lw["wo"], mode)
    h = rmsnorm(x, lw["mlp_norm"], d.eps)
    g = mm("btd,df->btf", h, lw["w_gate"], mode)
    u = mm("btd,df->btf", h, lw["w_up"], mode)
    return x + mm("btf,fd->btd", jax.nn.silu(g) * u, lw["w_down"], mode)


def hidden(w, tokens, c, mode="f32"):
    """Final-normed hidden states [B, T, D] of ``tokens [B, T]``."""
    d = dims(c)
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("layers/")}
    body = jax.checkpoint(lambda x, lw: (_layer(d, mode, x, lw), None))
    x, _ = jax.lax.scan(body, x, layers)
    return rmsnorm(x, w["final_norm"], d.eps)


def head(w, h, c, mode="f32"):
    if dims(c).tied:
        return mm("btd,vd->btv", h, w["embed"], mode)
    return mm("btd,dv->btv", h, w["lm_head"], mode)


def logits(w, tokens, c, mode="f32"):
    return head(w, hidden(w, tokens, c, mode), c, mode)


def loss_sum(w, tokens, labels, c, mode="f32"):
    """Summed next-token NLL of a block of rows."""
    return cross_entropy_sum(logits(w, tokens, c, mode), labels)
