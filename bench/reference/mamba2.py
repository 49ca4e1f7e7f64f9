"""Plain float32 reference of Mamba-2 (SSD), the attention-free language
model of arXiv:2405.21060 as released in ``state-spaces/mamba2-780m``.

Per layer (pre-norm residual): RMSNorm -> in_proj to [z, x, B, C, dt] ->
depthwise causal conv (width ``d_conv``, with bias) and SiLU over [x, B, C]
-> dt = softplus(dt + dt_bias), A = -exp(A_log) -> the selective state
recurrence, one step per token,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t,

with the state ``h`` of shape [heads, headdim, d_state] and B, C shared by
the heads of a group -> gated RMSNorm, rmsnorm(y * silu(z)) over the inner
width (one group) -> out_proj -> residual. A final RMSNorm, then logits
against the embedding matrix (``tie_embeddings``).

Departures from the published description: the recurrence runs token by
token instead of the chunked dual form (the same function of the inputs;
``chunk_size`` does not enter it); everything, the residual stream
included, is float32.

Parameters are a flat dict of float32 arrays; per-layer parameters are
stacked on a leading layer axis and named ``layers/<name>``.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from .numerics import cross_entropy_sum, mm, rmsnorm

_SCAN_BLOCK = 64   # tokens per rematerialised block of the recurrence

#: The harness's own tests at CPU size (``bench/tests/tiny.py``): the
#: published keys cut to a few dozen (a nested group's keys merged into
#: it). No Mamba-2 cell runs, so no limits are set at this size.
TINY = {
    "config": {"d_model": 64, "n_layer": 2, "vocab_size": 509,
               "padded_vocab_size": 512,
               "mamba2_module": {"headdim": 16, "d_state": 16,
                                 "chunk_size": 32}},
    "limits": {},
}


def dims(c: dict) -> SimpleNamespace:
    m = c["mamba2_module"]
    D = c["d_model"]
    di = m["expand"] * D
    H = di // m["headdim"]
    G, N = m["ngroups"], m["d_state"]
    return SimpleNamespace(
        L=c["n_layer"], D=D, di=di, H=H, P=m["headdim"], G=G, N=N,
        W=m["d_conv"], conv=di + 2 * G * N, proj=2 * di + 2 * G * N + H,
        Q=m["chunk_size"], V=c["padded_vocab_size"],
        eps=float(m["norm_epsilon"]),
        tied=bool(c["tie_embeddings"]))


def param_specs(c: dict) -> dict:
    d = dims(c)
    std = float(c.get("initializer_range", 0.02))
    normal = ("normal", std)
    norm = ("around_one", std)
    specs = {
        "embed": ((d.V, d.D), normal),
        "final_norm": ((d.D,), norm),
        "layers/norm": ((d.L, d.D), norm),
        "layers/in_proj": ((d.L, d.D, d.proj), normal),
        "layers/conv_w": ((d.L, d.W, d.conv), ("uniform", -0.5, 0.5)),
        "layers/conv_b": ((d.L, d.conv), ("uniform", -0.5, 0.5)),
        # A = -exp(A_log) with A uniform in [1, 16], as mamba_ssm initialises
        "layers/A_log": ((d.L, d.H), ("log_uniform", 1.0, 16.0)),
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        "layers/dt_bias": ((d.L, d.H), ("softplus_inverse_log_uniform",
                                        1e-3, 1e-1)),
        "layers/D": ((d.L, d.H), norm),
        "layers/gate_norm": ((d.L, d.di), norm),
        "layers/out_proj": ((d.L, d.di, d.D), normal),
    }
    if not d.tied:
        specs["lm_head"] = ((d.D, d.V), normal)
    return specs


def _causal_conv(x, w, b):
    """Depthwise causal conv: out[t] = sum_i w[i] * x[t - W + 1 + i] + b."""
    W, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, i:i + T] * w[i] for i in range(W)) + b


def _recurrence(xs, dt, A, Bm, Cm, Dskip, rep):
    """xs [B,T,H,P], dt [B,T,H], A [H], Bm/Cm [B,T,G,N] -> y [B,T,H,P]."""
    Bsz, T, H, P = xs.shape
    N = Bm.shape[-1]
    blk = min(_SCAN_BLOCK, T)
    pad = (-T) % blk
    if pad:   # trailing steps with dt = 0 leave the state and earlier y alone
        padt = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))  # noqa: E731
        xs, dt, Bm, Cm = padt(xs), padt(dt), padt(Bm), padt(Cm)
    Tp = T + pad

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                   # [B,H,P] [B,H] [B,G,N] x2
        bh = jnp.repeat(b_t, rep, axis=1)           # [B,H,N]
        ch = jnp.repeat(c_t, rep, axis=1)
        h = (jnp.exp(dt_t * A)[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[..., None] * bh[:, :, None, :])
        y = jnp.sum(h * ch[:, :, None, :], axis=-1) + Dskip[:, None] * x_t
        return h, y

    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    def to_blocks(a):     # [B, Tp, ...] -> [Tp/blk, blk, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((Tp // blk, blk) + a.shape[1:])

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(jax.checkpoint(block), h0,
                         tuple(to_blocks(a) for a in (xs, dt, Bm, Cm)))
    ys = ys.reshape((Tp,) + ys.shape[2:])
    return jnp.moveaxis(ys, 0, 1)[:, :T]


def _layer(d, mode, x, lw):
    Bsz, T, _ = x.shape
    h = rmsnorm(x, lw["norm"], d.eps)
    zxbcdt = mm("btd,de->bte", h, lw["in_proj"], mode)
    z, xbc, dt = jnp.split(zxbcdt, [d.di, d.di + d.conv], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, lw["conv_w"], lw["conv_b"]))
    xs, Bm, Cm = jnp.split(xbc, [d.di, d.di + d.G * d.N], axis=-1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])
    y = _recurrence(xs.reshape(Bsz, T, d.H, d.P), dt, A,
                    Bm.reshape(Bsz, T, d.G, d.N), Cm.reshape(Bsz, T, d.G, d.N),
                    lw["D"], d.H // d.G)
    y = rmsnorm(y.reshape(Bsz, T, d.di) * jax.nn.silu(z), lw["gate_norm"],
                d.eps)
    return x + mm("bte,ed->btd", y, lw["out_proj"], mode)


def hidden(w, tokens, c, mode="f32"):
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
    return cross_entropy_sum(logits(w, tokens, c, mode), labels)
