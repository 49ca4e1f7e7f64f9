"""Paper Fig 2a analog: convergence parity across parallelization plans.

The paper shows Modalities matching reference-framework loss curves at 8B.
Here we train the same reduced model under DDP / FSDP / FSDP×TP on 8
placeholder devices and assert the loss trajectories coincide — the
parallelization strategy must be loss-transparent.
"""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.optim.adamw import AdamW
    from repro.optim.schedules import warmup_cosine
    from repro.sharding import plans as PL
    from repro.train import steps as ST
    from repro.launch.mesh import make_local_mesh
    from repro.data.packed_dataset import ChunkedLMDataset, ShardedLoader

    cfg = get_reduced("llama3_8b").with_(n_layers=4)
    model = build_model(cfg)
    steps = {steps}
    # learnable synthetic stream: next token is a noisy affine function of
    # the current one, so CE can drop well below ln(V)
    import numpy as np
    prefix = "/tmp/repro_fig2a"
    rng = np.random.default_rng(9)
    n = 600000
    toks = np.empty(n, dtype=np.uint32)
    toks[0] = 3
    noise = rng.integers(0, 4, size=n)
    for i in range(1, n):
        toks[i] = (toks[i - 1] * 7 + 13 + noise[i]) % (cfg.vocab - 3) + 3
    toks.tofile(prefix + ".tokens.u32")
    np.save(prefix + ".docidx.npy", np.asarray([0, n], dtype=np.int64))
    from repro.data.packed_dataset import PackedDataset
    ds = PackedDataset(prefix)
    curves = {{}}
    for plan_name, dp, tp in [("ddp", 8, 1), ("fsdp", 8, 1), ("fsdp_tp", 4, 2)]:
        opt = AdamW(lr=warmup_cosine(3e-3, 10, steps))
        mesh = make_local_mesh(dp=dp, tp=tp)
        plan = PL.make_plan(plan_name)
        ctx = PL.mesh_context(plan, mesh)
        rng = jax.random.PRNGKey(0)
        pshapes = jax.eval_shape(model.init, rng)
        pspecs, _ = PL.param_shardings(plan, mesh, pshapes, model.param_axes())
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        state_sh = {{"params": pspecs,
                     "opt": {{"m": pspecs, "v": pspecs, "count": rep}},
                     "step": rep}}
        loader = ShardedLoader(ChunkedLMDataset(ds, 64, seed=0), global_batch=16)
        with mesh:
            state = jax.jit(lambda r: ST.init_train_state(model, opt, r),
                            out_shardings=state_sh)(rng)
            step = jax.jit(ST.make_train_step(model, opt, ctx),
                           in_shardings=(state_sh, None))
            losses = []
            for batch in loader.batches(steps):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        curves[plan_name] = losses
    print(json.dumps(curves))
""")


def run(steps: int = 25):
    script = SCRIPT.format(src=SRC, steps=steps)
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    curves = json.loads(proc.stdout.strip().splitlines()[-1])
    names = list(curves)
    ref = curves[names[0]]
    max_div = max(
        abs(curves[n][i] - ref[i])
        for n in names[1:]
        for i in range(len(ref))
    )
    return {
        "plans": names,
        "final_losses": {n: curves[n][-1] for n in names},
        "max_divergence": max_div,
        "converged": ref[-1] < ref[0],
        "curves": curves,
    }


if __name__ == "__main__":
    out = run()
    print(json.dumps({k: v for k, v in out.items() if k != "curves"}, indent=2))
