"""Plain AdamW training steps over a reference family's loss.

The loss of a batch is the mean next-token NLL over all its tokens,
computed in blocks of rows (each block rematerialised) so that a full-size
batch fits beside the weights and the optimizer state. AdamW as published
(Loshchilov & Hutter): optional clipping of the global gradient norm,
bias-corrected moments, decoupled weight decay on matrices only (not on
norm scales, biases or other vectors of a layer).
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _norms(tree: dict) -> dict:
    out = {}
    for k, a in tree.items():
        a = a.astype(jnp.float32)
        if k.startswith("layers/"):
            out[k] = jnp.sqrt(jnp.sum(jnp.square(a),
                                      axis=tuple(range(1, a.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(a)))[None]
    return out


_norms_jit = jax.jit(_norms)


def per_leaf_norms(flat: dict) -> dict:
    """name -> norm per layer (``layers/`` names, stacked on axis 0) or a
    1-element array; float32 sums on the device."""
    return {k: np.asarray(v, np.float64)
            for k, v in jax.device_get(_norms_jit(flat)).items()}


def delta_norms(after: dict, before: dict) -> dict:
    """Per-leaf norms of ``after - before`` (both on the device)."""
    return per_leaf_norms(jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(
        after, before))


def _decayed(name: str, a) -> bool:
    return (a.ndim - (1 if name.startswith("layers/") else 0)) >= 2


def make_step(ref, c: dict, opt: dict, mode: str, rows_per_block: int):
    lr, b1, b2 = float(opt["lr"]), float(opt["b1"]), float(opt["b2"])
    eps, wd = float(opt["eps"]), float(opt["weight_decay"])
    clip = float(opt.get("grad_clip", 0.0))

    def loss_fn(w, tokens, labels):
        B, S = tokens.shape
        nb = B // rows_per_block
        blocks = (tokens.reshape(nb, rows_per_block, S),
                  labels.reshape(nb, rows_per_block, S))

        def body(acc, blk):
            return acc + ref.loss_sum(w, blk[0], blk[1], c, mode), None

        total, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                                blocks)
        return total / tokens.size

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, count, tokens, labels):
        loss, g = jax.value_and_grad(loss_fn)(w, tokens, labels)
        if clip > 0:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
            g = {k: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
                 for k, x in g.items()}
        t = count.astype(jnp.float32)
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in w}
        v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in w}
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new_w = {}
        for k in w:
            upd = (m[k] / c1) / (jnp.sqrt(v[k] / c2) + eps)
            if wd > 0 and _decayed(k, w[k]):
                upd = upd + wd * w[k]
            new_w[k] = w[k] - lr * upd
        return new_w, m, v, loss, _norms(g)

    return step


def adamw_steps(ref, c: dict, w: dict, batches: List[tuple], opt: dict,
                mode: str = "f32", rows_per_block: int = 1,
                half_batch: bool = False) -> Dict:
    """Train ``w`` (flat dict on the device; donated) over ``batches`` of
    ``(tokens, labels)``. Returns the per-step losses, the per-leaf norms
    of the first gradient as the optimizer gets it, and the final weights.
    ``half_batch`` plants a fault: each step sees only the first half of
    its rows."""
    step = make_step(ref, c, opt, mode, rows_per_block)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, g1 = [], None
    for i, (tok, lab) in enumerate(batches):
        if half_batch:
            tok, lab = tok[:len(tok) // 2], lab[:len(lab) // 2]
        w, m, v, loss, gn = step(w, m, v, jnp.int32(i + 1),
                                 jnp.asarray(tok, jnp.int32),
                                 jnp.asarray(lab, jnp.int32))
        if g1 is None:
            g1 = {k: np.asarray(x, np.float64)
                  for k, x in jax.device_get(gn).items()}
        losses.append(float(loss))
    del m, v
    return {"losses": losses, "grad_norms": g1, "weights": w}
