"""Mamba2 SSD chunk-scan Pallas TPU kernel.

Grid (B, H, n_chunks) with the chunk dimension innermost and sequential; the
inter-chunk SSM state [P, N] lives in VMEM scratch. Within a chunk the dual
(quadratic) form runs on the MXU: C·Bᵀ [Q,Q] and the [Q,Q]·[Q,P] combine.
Chunk Q and head dims are chosen MXU-aligned (Q=128, N,P multiples of 8).
``dt`` enters as a row and as a column so every block's last two dims are
legal tiles; ``A`` and ``D_skip`` are whole [H] vectors in scalar memory.

Group broadcast (n_groups < H) happens in the index maps — B/C tiles are
indexed by h // heads_per_group, never repeated in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import pallas_call


def _ssd_body(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, dsk_ref, y_ref,
              h_ref, *, chunk: int):
    h_idx = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # every intermediate is 2-D: dt arrives both as a row [1, Q] and as a
    # column [Q, 1], so the cumulative sums and their broadcasts need no
    # in-kernel transpose or 1-D vector
    x = x_ref[0, 0].astype(jnp.float32)        # [Q, P]
    dt_row = dtr_ref[0, 0]                     # [1, Q]
    dt_col = dtc_ref[0, 0]                     # [Q, 1]
    A = a_ref[h_idx]                           # scalar (negative), SMEM
    D = dsk_ref[h_idx]                         # scalar, SMEM
    Bm = b_ref[0, 0].astype(jnp.float32)       # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)       # [Q, N]

    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iq >= jq
    a_row = dt_row * A                          # log-decay
    a_col = dt_col * A
    Sa_col = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1, keepdims=True)
    Sa_row = jnp.sum(jnp.where(iq <= jq, a_col, 0.0), axis=0, keepdims=True)
    a_tot = jnp.sum(a_row, axis=1, keepdims=True)                    # [1,1]
    # intra-chunk quadratic form
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))      # [Q,Q]
    L = jnp.where(causal, jnp.exp(Sa_col - Sa_row), 0.0)
    M = CB * L * dt_row
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())))          # [Q,P]
    # inter-chunk contribution
    h = h_ref[...]                                                   # [P,N] f32
    y = y + jnp.exp(Sa_col) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ()))
    )
    y = y + D * x
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h' = exp(Sa_Q) h + sum_j decay_j dt_j x_j B_j
    decay_out = jnp.exp(a_tot - Sa_col) * dt_col                     # [Q,1]
    dBx = jax.lax.dot_general(x, Bm * decay_out,
                              (((0,), (0,)), ((), ())))              # [P,N]
    h_ref[...] = jnp.exp(a_tot) * h + dBx


def ssd_scan(x, dt, A, Bm, Cm, D_skip, *, chunk: int = 128):
    """x [B,S,H,P]; dt [B,S,H] (post-softplus); A [H] (negative);
    Bm/Cm [B,S,G,N]; D_skip [H]. Returns y [B,S,H,P]."""
    Bq, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    assert S % chunk == 0, f"S={S} not a multiple of chunk={chunk}"
    nc = S // chunk

    xt = x.transpose(0, 2, 1, 3)                   # [B,H,S,P]
    dtt = dt.astype(jnp.float32).transpose(0, 2, 1)  # [B,H,S]
    Bt = Bm.transpose(0, 2, 1, 3)                  # [B,G,S,N]
    Ct = Cm.transpose(0, 2, 1, 3)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole [H] vector

    kernel = functools.partial(_ssd_body, chunk=chunk)
    y = pallas_call(
        kernel,
        grid=(Bq, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            smem,
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            smem,
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bq, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )(xt, dtt[:, :, None, :], dtt[..., None], A.astype(jnp.float32), Bt, Ct,
      D_skip.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3)
