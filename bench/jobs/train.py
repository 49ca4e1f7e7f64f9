"""Training job: ``Gym.run``, the program's training loop, with its jitted
train step fed by the sharded loader with device prefetch, over packed
token rows made from the seed.

Set-up builds one gym and its state from the seed's weights (``Gym.setup``
has no argument for given weights, so its ``_init_state`` is replaced for
that one call), and drives the first three optimizer steps as three calls
of ``Gym.run`` (step 1 compiles). What the comparison reads is taken
there: the losses the loop logs, the first gradient from Adam's first
moment after step 1, and the parameters after step 3. The same gym and
state then run the window: one ``Gym.run`` call of as many steps as the
checked steps' pace fits into ``--seconds``, logging every ``log_every``
steps as a user's run does; it ends once the last step is done.

Once the window has closed and the program's state is freed, the plain
reference trains from the same weights on the same rows for three steps.

A workload file that gives ``"plan"`` (a name in the program's plan
catalog, or the fields of a custom plan) and ``"mesh"`` (axis sizes, e.g.
``{"data": 4, "model": 1}``) trains on a mesh of the chips the harness
took, through the registry's ``mesh_provider`` and ``sharding_plan`` as a
user's run does; the seed's weights then come drawn across those chips.
"""
from __future__ import annotations

import os
import time

import numpy as np

CHECKED_STEPS = 3


def write_rows(rows: np.ndarray, prefix: str) -> None:
    """Rows as the program's packed dataset: a uint32 token stream and its
    document index (one document per row)."""
    from repro.data.tokenize_pipeline import DOCIDX_SUFFIX, TOKENS_SUFFIX

    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    rows.astype(np.uint32).tofile(prefix + TOKENS_SUFFIX)
    np.save(prefix + DOCIDX_SUFFIX,
            np.arange(rows.shape[0] + 1, dtype=np.int64) * rows.shape[1])


def batches_of(rows: np.ndarray, batch: int, n: int):
    return [(rows[k * batch:(k + 1) * batch, :-1],
             rows[k * batch:(k + 1) * batch, 1:]) for k in range(n)]


def mesh_and_plan(ctx, registry):
    """The workload's mesh over ``ctx.devices`` and its sharding plan, or
    ``(None, None)`` where it gives neither."""
    spec = ctx.spec
    if "plan" not in spec and "mesh" not in spec:
        return None, None
    if "plan" not in spec or "mesh" not in spec:
        raise ValueError(f"{ctx.name}: a workload gives both 'plan' and "
                         f"'mesh', or neither")
    from jax.sharding import Mesh

    axes = spec["mesh"]
    if int(np.prod(list(axes.values()))) != len(ctx.devices):
        raise ValueError(f"{ctx.name}: mesh {axes} does not cover the "
                         f"cell's {len(ctx.devices)} chips")
    mesh = Mesh(np.asarray(ctx.devices).reshape(tuple(axes.values())),
                tuple(axes))
    plan = spec["plan"]
    if isinstance(plan, str):
        return mesh, registry.build("sharding_plan", plan)
    return mesh, registry.build("sharding_plan", "custom", **plan)


def build(ctx):
    """The program's objects, built through the registry as the run API
    builds them, and the data of this seed."""
    import jax
    import jax.numpy as jnp
    from repro.config.registry import DEFAULT_REGISTRY as REG
    import repro.core.components  # noqa: F401  (populates the registry)

    from bench.traffic import generate

    spec, c = ctx.spec, ctx.config
    B, S = spec["global_batch"], spec["seq_len"]
    rows = generate.token_rows(ctx.traffic, B * spec["data_steps"], S,
                               ctx.seed, c["vocab_size"])
    prefix = os.path.join(ctx.scratch, "data", "tokens")
    write_rows(rows, prefix)
    model = REG.build("model", "auto", arch_config=ctx.prog.arch_config(c))
    dataset = REG.build("dataset", "packed_chunked", prefix=prefix,
                        seq_len=S, seed=0, shuffle=False)
    loader = REG.build("loader", "sharded", dataset=dataset, global_batch=B)
    opt = REG.build("optimizer", "adamw", **spec["optimizer"])
    mesh, plan = mesh_and_plan(ctx, REG)
    sharded = {} if mesh is None else {"mesh_provider": mesh,
                                       "sharding_plan": plan}
    gym = REG.build("gym", "standard", model=model, optimizer=opt,
                    loader=loader, prefetch=spec["prefetch"],
                    log_every=spec["log_every"], **sharded)
    params = ctx.to_program(ctx.weights())
    if mesh is None:
        gym._init_state = lambda: {"params": params, "opt": opt.init(params),
                                   "step": jnp.zeros((), jnp.int32)}
    else:
        # from the weights' placement into the plan's layout of the state
        gym._init_state = lambda: jax.jit(
            lambda p: {"params": p, "opt": opt.init(p),
                       "step": jnp.zeros((), jnp.int32)},
            out_shardings=gym._state_sh)(params)
    state = gym.setup()
    del gym._init_state
    for hook in ctx.hooks:
        hook(ctx, gym)
    return {"gym": gym, "state": state, "rows": rows, "B": B, "S": S}


def first_steps(ctx, b):
    """Steps 1-3, each one call of ``Gym.run``, the window's own call and
    feed; returns the state, the wall time of one checked step after the
    first (which compiles) and what the comparison reads of the program."""
    from bench.reference.training import per_leaf_norms

    gym = b["gym"]
    state, losses, prog, times = b["state"], [], {}, []
    b["state"] = None
    b1 = float(ctx.spec["optimizer"]["b1"])
    for k in range(1, CHECKED_STEPS + 1):
        t0 = time.perf_counter()
        out = gym.run(1, state=state)
        times.append(time.perf_counter() - t0)
        state = out["state"]
        # the first step of every call is logged, whatever ``log_every``
        losses += [h["loss"] for h in out["history"] if "loss" in h]
        if k == 1:
            with ctx.capture():
                g = per_leaf_norms(ctx.from_program(state["opt"]["m"]))
                prog["grad_norms"] = {n: v / (1.0 - b1) for n, v in g.items()}
    with ctx.capture():
        import jax

        prog["params_after"] = jax.device_get(ctx.from_program(
            state["params"]))
        prog["losses"] = losses
    return state, min(times[1:]), prog


def window(ctx, b, state, step_s):
    """One ``Gym.run`` call of as many steps as the checked steps' pace
    fits into ``--seconds``; it returns once the last step's counter is
    back on the host."""
    n = max(1, int(round(ctx.seconds / step_s)))
    ctx.window_begins()
    t0 = time.perf_counter()
    out = b["gym"].run(n, state=state)
    window_s = time.perf_counter() - t0
    ctx.window_ends()
    losses = np.asarray([h["loss"] for h in out["history"] if "loss" in h],
                        np.float64)
    return out["state"], n, window_s, losses


def traced_steps(ctx, b, state):
    """With ``--trace 1``: one more ``Gym.run`` call of ``trace_steps``
    under the profiler, after the timed window, so that the profiler's own
    cost stays out of every host-clock reading."""
    gym = b["gym"]
    step = gym._step

    def spanned(s, batch):
        with ctx.span("step_dispatch"):
            return step(s, batch)

    gym._step = spanned
    ctx.trace_start()
    state = gym.run(ctx.spec["trace_steps"], state=state)["state"]
    ctx.trace_stop()
    gym._step = step
    return state


def log_step_memory(ctx, b, state):
    """The compiler's memory analysis of the train step at the window's
    shapes (read from the compilation cache); on a mesh, with the state's
    and the batch's shardings, the bytes of each chip."""
    import jax

    gym = b["gym"]
    batch = next(iter(gym.loader.batches(1)))
    if gym.mesh is None:
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (state, batch))
        ctx.log_memory("train_step", gym._jit_step.lower(*shapes))
        return

    def placed(a, sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    shapes = (jax.tree_util.tree_map(lambda a: placed(a, a.sharding), state),
              jax.tree_util.tree_map(placed, batch,
                                     gym._batch_shardings(batch)))
    ctx.log_memory(f"train_step, each of {len(ctx.devices)} chips",
                   gym._jit_step.lower(*shapes))


def program_update_norms(ctx, params_after):
    """Per-leaf norms of the program's parameter change over the checked
    steps, against the seed's weights made anew."""
    import jax

    from bench.reference.training import delta_norms

    after = jax.device_put(params_after, ctx.placement())
    return delta_norms(after, ctx.weights())


def reference_readings(ctx, rows, mode="f32", half_batch=False):
    from bench.reference.training import adamw_steps, delta_norms

    spec = ctx.spec
    out = adamw_steps(ctx.ref, ctx.config, ctx.weights(),
                      batches_of(rows, spec["global_batch"], CHECKED_STEPS),
                      spec["optimizer"], mode=mode,
                      rows_per_block=spec["reference_rows_per_block"],
                      half_batch=half_batch)
    out["update_norms"] = delta_norms(out.pop("weights"), ctx.weights())
    return out


def run(ctx):
    from bench import checks, flops

    b = build(ctx)
    state, step_s, prog = first_steps(ctx, b)
    state, n, window_s, losses = window(ctx, b, state, step_s)
    if ctx.trace:
        state = traced_steps(ctx, b, state)
    log_step_memory(ctx, b, state)
    del state
    B, S = b["B"], b["S"]
    ctx.attempted = n
    ctx.failed = int(np.sum(~np.isfinite(losses)))
    step_flops = flops.train_step(ctx.ref, ctx.config, B, S)
    ctx.record.update({"window_s": window_s, "steps": n,
                       "tokens": n * B * S, "flops": n * step_flops})
    ctx.log_detail(f"window: {n} steps in {window_s} s (checked step "
                   f"{step_s} s)")
    b["gym"] = None
    prog["update_norms"] = program_update_norms(ctx, prog.pop("params_after"))
    ref = reference_readings(ctx, b["rows"])
    for name, (value, where) in checks.train_readings(prog, ref).items():
        ctx.check(name, value, ctx.spec["limits"][name])
        ctx.log_detail(f"{name}: worst at {where}")
