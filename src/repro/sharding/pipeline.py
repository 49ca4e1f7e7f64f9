"""Pipeline parallelism: the GPipe stage/microbatch schedule, two ways.

The paper lists pipeline parallelism among its composable strategies. On a
multi-pod TPU system the natural placement is ACROSS pods: each pod holds a
contiguous stage of layers, activations flow pod→pod over DCN/ICI once per
microbatch, and cross-pod traffic drops from per-layer FSDP collectives to
one activation tensor per microbatch per stage boundary.

Layers stacked ``[L, ...]`` are split into S stages ``[S, L/S, ...]``
sharded over the ``pipe`` mesh axis; the GPipe schedule runs ``S + M - 1``
ticks for M microbatches (bubble fraction ``(S-1)/(S+M-1)``).

Two engines share that schedule:

* :func:`gpipe_apply` — explicit SPMD via ``shard_map`` + ``lax.ppermute``.
  Every device runs the same tick program; activations rotate one stage
  forward per tick. Self-contained and forward-only in spirit (the
  reference/demo path).

* :func:`pipeline_apply` — the *training* path: pure auto-sharding SPMD.
  The stage dim is a ``vmap`` axis whose shards live on the ``pipe`` mesh
  axis; the stage shift is ``jnp.roll`` under a sharding constraint (XLA
  lowers it to a collective-permute). Because it never leaves auto mode,
  TP ``with_sharding_constraint``s and the MoE expert-parallel
  ``shard_map`` inside the stage body compose unchanged, and ``jax.grad``
  transposes the schedule into the pipelined backward — microbatch
  gradient accumulation falls out of autodiff. Carries are pytrees, so
  auxiliary losses (MoE router balance) ride alongside activations.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def gpipe_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,            # leaves [n_stages, ...] (sharded over pipe)
    x: jax.Array,                 # [n_micro, micro_batch, S, D] microbatched
    mesh,
    pipe_axis: str = "pipe",
) -> jax.Array:
    """Run x through n_stages sequential stages with a GPipe schedule.

    Returns [n_micro, micro_batch, S, D] outputs (from the last stage).
    """
    n_stages = mesh.shape[pipe_axis]
    n_micro = x.shape[0]
    if n_micro < 1:
        raise ValueError("gpipe_apply needs at least one microbatch")

    if n_stages == 1:
        # degenerate single-stage "pipeline": no rotation, no masking —
        # just the stage body over each microbatch (M ticks, zero bubble)
        params0 = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        return jax.lax.map(lambda xm: stage_fn(params0, xm), x)

    def local(params_local, x_all):
        # params_local: this device's stage params [1, ...] -> [...]
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(pipe_axis)
        n_ticks = n_stages + n_micro - 1
        buf = jnp.zeros_like(x_all[0])          # activation in flight
        outs = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t while valid, then recirculated
            # garbage (masked on write-out) once the injections run dry
            mb = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(t < n_micro, 1.0, 0.0).astype(x_all.dtype)
            x_in = jnp.where(
                stage == 0,
                x_all[mb] * inject + buf * (1 - inject),
                buf,
            )
            # every stage computes (garbage flows are masked on write-out)
            y = stage_fn(params_local, x_in)
            # last stage writes its result for microbatch t - (n_stages - 1)
            out_mb = t - (n_stages - 1)
            valid_out = (stage == n_stages - 1) & (out_mb >= 0)
            outs = jax.lax.cond(
                valid_out,
                lambda o: o.at[jnp.clip(out_mb, 0, n_micro - 1)].set(y),
                lambda o: o,
                outs,
            )
            # rotate activations one stage forward
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, pipe_axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast them to all
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            pipe_axis,
        )
        return outs

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(pipe_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule; 0 for the S=1 degenerate case."""
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_stages == 1:
        return 0.0
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")
    return (n_stages - 1) / (n_stages + n_micro - 1)


def effective_n_micro(n_micro: int, n_stages: int, global_batch: int = 0) -> int:
    """The microbatch count the schedule actually uses: ``n_micro`` (or the
    ``2 * n_stages`` GPipe default) reduced to the largest divisor of the
    global batch so every microbatch is equal-sized."""
    m = n_micro or 2 * n_stages
    if global_batch:
        m = min(m, global_batch)
        while global_batch % m:
            m -= 1
    return max(m, 1)


# ---------------------------------------------------------------------------
# staging / microbatching views (shard-boundary-respecting reshapes)
# ---------------------------------------------------------------------------
def stage_split(tree: Any, n_stages: int) -> Any:
    """``[L, ...]`` leaves -> ``[S, L/S, ...]``. With the LAYER dim sharded
    over ``pipe`` into S contiguous chunks this reshape is local to each
    device — the staged view IS the stored layout, just rank-split."""

    def split(a):
        L = a.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])

    return jax.tree_util.tree_map(split, tree)


def microbatch(tree: Any, n_micro: int) -> Any:
    """``[B, ...]`` leaves -> ``[M, B/M, ...]``."""

    def split(a):
        bsz = a.shape[0]
        if bsz % n_micro:
            raise ValueError(f"batch {bsz} not divisible by {n_micro} microbatches")
        return a.reshape((n_micro, bsz // n_micro) + a.shape[1:])

    return jax.tree_util.tree_map(split, tree)


def unmicrobatch(tree: Any) -> Any:
    """Inverse of :func:`microbatch`: ``[M, mb, ...]`` -> ``[B, ...]``."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), tree)


# ---------------------------------------------------------------------------
# auto-sharding SPMD pipeline (the training path)
# ---------------------------------------------------------------------------
def pipeline_apply(
    stage_fn: Callable[[Any, Any], Any],
    staged_params: Any,     # leaves [S, L/S, ...], stage dim sharded over pipe
    micro: Any,             # per-microbatch carry pytree, leaves [M, mb, ...]
    mesh,
    pipe_axis: str = "pipe",
    dp_axes: Tuple[str, ...] = (),
) -> Any:
    """GPipe in pure auto-sharding SPMD: returns ``micro``'s structure with
    every microbatch pushed through all S stages in schedule order.

    ``stage_fn(params_slice, carry) -> carry`` is ONE stage's work (e.g. a
    ``Stacked.fold`` over its L/S local layers); it is ``vmap``-ed over the
    stage dim, which XLA partitions over ``pipe_axis``. The stage shift is
    ``jnp.roll`` + a sharding constraint (lowered to collective-permute).
    Differentiable end-to-end; ``jax.grad`` yields the pipelined backward.
    """
    leaves = jax.tree_util.tree_leaves(micro)
    if not leaves:
        return micro
    n_micro = leaves[0].shape[0]
    s_leaves = jax.tree_util.tree_leaves(staged_params)
    n_stages = s_leaves[0].shape[0] if s_leaves else 1

    if n_stages == 1:
        params0 = jax.tree_util.tree_map(lambda a: a[0], staged_params)
        return jax.lax.map(lambda c: stage_fn(params0, c), micro)

    def cst_state(tree):
        # state leaves [S, mb, ...]: stage dim over pipe, microbatch over dp
        def one(a):
            spec = [None] * a.ndim
            spec[0] = pipe_axis
            if dp_axes and a.ndim >= 2:
                dps = 1
                for ax in dp_axes:
                    dps *= mesh.shape[ax]
                if a.shape[1] % dps == 0 and a.shape[1] > 0:
                    spec[1] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(*spec)))

        return jax.tree_util.tree_map(one, tree)

    state = cst_state(jax.tree_util.tree_map(
        lambda l: jnp.zeros((n_stages,) + l.shape[1:], l.dtype), micro))
    outs = jax.tree_util.tree_map(jnp.zeros_like, micro)

    def tick(carry, t):
        state, outs = carry
        mb = jnp.clip(t, 0, n_micro - 1)
        inj = t < n_micro
        state = jax.tree_util.tree_map(
            lambda s, xm: s.at[0].set(jnp.where(inj, xm[mb], s[0])),
            state, micro)
        state = cst_state(state)
        y = jax.vmap(stage_fn)(staged_params, state)
        y = cst_state(y)
        out_mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        take = t >= n_stages - 1
        outs = jax.tree_util.tree_map(
            lambda o, yl: o.at[out_mb].set(
                jnp.where(take, yl[n_stages - 1], o[out_mb])),
            outs, y)
        state = cst_state(jax.tree_util.tree_map(
            lambda yl: jnp.roll(yl, 1, axis=0), y))
        return (state, outs), None

    (state, outs), _ = jax.lax.scan(
        tick, (state, outs), jnp.arange(n_stages + n_micro - 1))
    return outs
