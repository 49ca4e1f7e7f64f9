"""Mixture-of-Experts: shared experts + routed experts (top-k).

Two compute paths, numerically equivalent (tested):

* ``dense`` — every expert over every token, gate-weighted. O(E·T) FLOPs;
  the oracle for tests and the single-device smoke path (small E only).
* ``ep`` — expert parallelism inside ``shard_map``: tokens sharded over the
  DP axes and replicated over the TP axis; experts sharded over the TP axis
  (and their d_model dim *storage*-sharded over the FSDP axes, all-gathered
  on use — FSDP semantics made explicit). Each rank selects up to
  ``capacity`` token-assignments routed to its local experts (argsort
  select), runs them through ``jax.lax.ragged_dot`` grouped matmuls, scatters
  back, and ``psum``s over the TP axis to combine expert partial outputs.

Routing: softmax -> top-k -> renormalize (deepseek-style); load-balance aux
loss computed on the full router distribution.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import base as B
from .common import dense_init


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_moe(cfg: B.ArchConfig, rng) -> Dict[str, Any]:
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_routed, m.d_expert
    r = jax.random.split(rng, 7)
    p = {
        "router": dense_init(r[0], (D, E), D),
        "w_gate": dense_init(r[1], (E, D, F), D),
        "w_up": dense_init(r[2], (E, D, F), D),
        "w_down": dense_init(r[3], (E, F, D), F),
    }
    if m.n_shared:
        Fs = m.n_shared * F
        p["shared"] = {
            "w_gate": dense_init(r[4], (D, Fs), D),
            "w_up": dense_init(r[5], (D, Fs), D),
            "w_down": dense_init(r[6], (Fs, D), Fs),
        }
    return p


def moe_axes(cfg: B.ArchConfig) -> Dict[str, Any]:
    p = {
        "router": (B.D_MODEL, None),
        "w_gate": (B.EXPERTS, B.D_MODEL, B.D_EXPERT),
        "w_up": (B.EXPERTS, B.D_MODEL, B.D_EXPERT),
        "w_down": (B.EXPERTS, B.D_EXPERT, B.D_MODEL),
    }
    if cfg.moe.n_shared:
        p["shared"] = {
            "w_gate": (B.D_MODEL, B.D_FF),
            "w_up": (B.D_MODEL, B.D_FF),
            "w_down": (B.D_FF, B.D_MODEL),
        }
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------
def route(cfg: B.ArchConfig, router_w, x_flat):
    """x_flat [T, D] -> (topk_idx [T,k], topk_gate [T,k], aux_loss scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    # load-balance loss (Switch-style): E * sum_e f_e * P_e
    E = m.n_routed
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # [T,k,E]
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)             # fraction routed
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pmean) * m.router_aux_coef
    return idx, gate, aux


# ---------------------------------------------------------------------------
# dense oracle path
# ---------------------------------------------------------------------------
def _expert_ffn(xs, wg, wu, wd):
    h = jax.nn.silu(xs @ wg.astype(xs.dtype)) * (xs @ wu.astype(xs.dtype))
    return h @ wd.astype(xs.dtype)


def moe_dense(cfg: B.ArchConfig, p, x_flat, idx, gate):
    """All experts over all tokens; gate-weighted combine. Oracle path."""
    m = cfg.moe
    outs = jnp.einsum(
        "tef,efd->ted",
        jax.nn.silu(jnp.einsum("td,edf->tef", x_flat, p["w_gate"].astype(x_flat.dtype)))
        * jnp.einsum("td,edf->tef", x_flat, p["w_up"].astype(x_flat.dtype)),
        p["w_down"].astype(x_flat.dtype),
    )  # [T, E, D]
    onehot = jax.nn.one_hot(idx, m.n_routed, dtype=x_flat.dtype)  # [T,k,E]
    comb = jnp.einsum("tk,tke->te", gate.astype(x_flat.dtype), onehot)
    return jnp.einsum("te,ted->td", comb, outs)


# ---------------------------------------------------------------------------
# expert-parallel path (inside shard_map)
# ---------------------------------------------------------------------------
def _capacity(T: int, k: int, ep: int, cf: float) -> int:
    total = T * k
    if total <= 4096:
        return total  # dropless for small token counts (decode)
    c = int(math.ceil(cf * total / ep))
    return min(total, ((c + 127) // 128) * 128)


def _ep_local(cfg, x_loc, idx_loc, gate_loc, wg, wu, wd, *, ep_axes,
              ep_axis_sizes, storage_axes, ep_size):
    """Per-device EP body. x_loc [T,D]; idx/gate [T,k]; w* [E_loc, D(/fsdp), F]."""
    m = cfg.moe
    T, D = x_loc.shape
    k = m.top_k
    E_loc = m.n_routed // ep_size
    # flattened (row-major) rank over the EP axes
    rank = jnp.int32(0)
    for ax, sz in zip(ep_axes, ep_axis_sizes):
        rank = rank * sz + jax.lax.axis_index(ax)
    e0 = rank * E_loc

    # FSDP storage gather: experts' d_model dim was storage-sharded.
    if storage_axes:
        wg = jax.lax.all_gather(wg, storage_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, storage_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, storage_axes, axis=2, tiled=True)

    eids = idx_loc.reshape(-1)                      # [T*k]
    gates = gate_loc.reshape(-1)
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    local = (eids >= e0) & (eids < e0 + E_loc)

    # per-(device, expert) capacity buckets: the grouped matmul then runs as
    # one batched dot [E_loc, C_e, D] x [E_loc, D, F] with true grouped-GEMM
    # flops (jax.lax.ragged_dot lowers densely on the CPU backend, inflating
    # compiled flops E_loc-fold; bucketing is also the TPU-friendly layout).
    C_total = _capacity(T, k, ep_size, m.capacity_factor)
    C_e = max(8, -(-int(C_total * m.capacity_factor) // E_loc))
    leid = jnp.where(local, eids - e0, E_loc)       # E_loc = overflow bucket
    onehot = jax.nn.one_hot(leid, E_loc + 1, dtype=jnp.int32)   # [T*k, E+1]
    pos = jnp.cumsum(onehot, axis=0) - 1                        # pos in expert
    pos = jnp.sum(pos * onehot, axis=1)                         # [T*k]
    keep = local & (pos < C_e)
    bidx = jnp.where(keep, leid, E_loc)             # drop -> overflow bucket
    bpos = jnp.where(keep, pos, 0)

    xs = x_loc[tok]                                 # [T*k, D] gather
    buckets = jnp.zeros((E_loc + 1, C_e, D), x_loc.dtype)
    buckets = buckets.at[bidx, bpos].add(jnp.where(keep[:, None], xs, 0.0))
    xb = buckets[:E_loc]                            # [E_loc, C_e, D]

    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xb, wg.astype(xb.dtype))
    ) * jnp.einsum("ecd,edf->ecf", xb, wu.astype(xb.dtype))
    yb = jnp.einsum("ecf,efd->ecd", h, wd.astype(xb.dtype))  # [E_loc, C_e, D]

    ys = yb[jnp.where(keep, bidx, 0), jnp.where(keep, bpos, 0)]  # [T*k, D]
    ys = ys * (gates * keep).astype(ys.dtype)[:, None]
    out = jnp.zeros((T, D), ys.dtype).at[tok].add(ys)
    return jax.lax.psum(out, ep_axes)


def moe_ep(cfg: B.ArchConfig, p, x_flat, idx, gate, mesh_ctx: B.MeshContext,
           storage_axes: Tuple[str, ...] = ()):
    """Expert-parallel routed experts via shard_map.

    x_flat [T_global, D] sharded over dp axes; experts sharded over tp axis.
    """
    ep_axes = tuple(mesh_ctx.ep_axes)
    ep_size = mesh_ctx.ep_size
    # tokens shard over dp axes not used by EP (divisibility permitting);
    # otherwise replicate tokens (tiny decode batches / EP-over-everything)
    free_dp = tuple(a for a in mesh_ctx.dp_axes if a not in ep_axes)
    import math as _m

    free_size = _m.prod(mesh_ctx.mesh.shape[a] for a in free_dp) if free_dp else 1
    dp_ok = free_dp and x_flat.shape[0] % free_size == 0
    dp = P(free_dp) if dp_ok else P()
    e_spec = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    w_specs = (
        P(e_spec, storage_axes if storage_axes else None, None),
        P(e_spec, storage_axes if storage_axes else None, None),
        P(e_spec, None, storage_axes if storage_axes else None),
    )
    fn = functools.partial(
        _ep_local,
        cfg,
        ep_axes=ep_axes,
        ep_axis_sizes=tuple(mesh_ctx.mesh.shape[a] for a in ep_axes),
        storage_axes=storage_axes if storage_axes else (),
        ep_size=ep_size,
    )
    return jax.shard_map(
        fn,
        mesh=mesh_ctx.mesh,
        in_specs=(P(*dp, None), P(*dp, None), P(*dp, None)) + w_specs,
        out_specs=P(*dp, None),
        check_vma=False,
    )(x_flat, idx, gate, p["w_gate"], p["w_up"], p["w_down"])


# ---------------------------------------------------------------------------
# full layer
# ---------------------------------------------------------------------------
def moe_forward(cfg: B.ArchConfig, p, x, mesh_ctx: Optional[B.MeshContext] = None,
                storage_axes: Tuple[str, ...] = ()) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,D] -> (out [B,S,D], aux_loss). Routed + shared experts."""
    Bq, S, D = x.shape
    x_flat = x.reshape(Bq * S, D)
    idx, gate, aux = route(cfg, p["router"], x_flat)
    use_ep = (
        mesh_ctx is not None
        and mesh_ctx.ep_enabled
        and mesh_ctx.tp_axis is not None
        and cfg.moe.n_routed % mesh_ctx.ep_size == 0
    )
    if use_ep:
        routed = moe_ep(cfg, p, x_flat, idx, gate, mesh_ctx, storage_axes)
    else:
        routed = moe_dense(cfg, p, x_flat, idx, gate)
    out = routed.reshape(Bq, S, D)
    if cfg.moe.n_shared:
        s = p["shared"]
        h = jax.nn.silu(
            jnp.einsum("bsd,df->bsf", x, s["w_gate"].astype(x.dtype))
        ) * jnp.einsum("bsd,df->bsf", x, s["w_up"].astype(x.dtype))
        out = out + jnp.einsum("bsf,fd->bsd", h, s["w_down"].astype(x.dtype))
    return out, aux
