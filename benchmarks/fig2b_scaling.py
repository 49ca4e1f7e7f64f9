"""Paper Fig 2b analog: strong scaling.

Two views:
1. MEASURED — tokens/s for the same tiny model on 1/2/4/8 placeholder CPU
   devices (DDP), each in a fresh subprocess (device count locks at init).
2. MODELED — llama3-8b step time on TPU v5e as max(compute, memory,
   collective) from the analytic roofline at DP degrees 16..1024, with the
   α–β ICI collective model (this is where the paper's latency wall at
   DP=1024 shows up, and where the FSDP-unit dial recovers it).
"""
import json
import math
import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, SRC)
from repro.telemetry.accounting import V5E  # noqa: E402

# one timing implementation: the measured rows drive the `bench` run kind
# (Gym.bench) on a declarative run doc instead of a hand-rolled step loop
_MEASURE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
    import json, sys
    sys.path.insert(0, {src!r})
    from repro.run.api import execute_doc

    ndev = {ndev}
    B, S = ndev * 4, 128
    doc = {{
        "run": {{"kind": "bench", "name": f"fig2b_{{ndev}}dev",
                 "output_dir": f"/tmp/repro_fig2b_{{ndev}}dev",
                 "bench": {{"steps": 5, "warmup": 1, "bench_dir": ""}}}},
        "arch": {{"component_key": "arch_config", "variant_key": "stablelm_1p6b",
                  "config": {{"reduced": True, "n_layers": 2}}}},
        "model": {{"component_key": "model", "variant_key": "auto",
                   "config": {{"arch_config": {{"instance_key": "arch"}}}}}},
        "optimizer": {{"component_key": "optimizer", "variant_key": "adamw",
                       "config": {{"lr": 0.001}}}},
        "dataset": {{"component_key": "dataset", "variant_key": "synthetic",
                     "config": {{"n_tokens": B * (S + 1) * 16, "vocab": 512,
                                 "prefix": f"/tmp/repro_fig2b_data_{{ndev}}",
                                 "seq_len": S}}}},
        "loader": {{"component_key": "loader", "variant_key": "sharded",
                    "config": {{"dataset": {{"instance_key": "dataset"}},
                                "global_batch": B}}}},
        "mesh": {{"component_key": "mesh_provider", "variant_key": "local",
                  "config": {{"dp": ndev, "tp": 1}}}},
        "plan": {{"component_key": "sharding_plan", "variant_key": "ddp"}},
        "gym": {{"component_key": "gym", "variant_key": "standard",
                 "config": {{"model": {{"instance_key": "model"}},
                             "optimizer": {{"instance_key": "optimizer"}},
                             "loader": {{"instance_key": "loader"}},
                             "mesh_provider": {{"instance_key": "mesh"}},
                             "sharding_plan": {{"instance_key": "plan"}}}}}},
    }}
    res = execute_doc(doc, write_files=False)
    dt = res["steady_step_ms"] / 1000.0
    print(json.dumps({{"ndev": ndev, "step_s": dt,
                       "tokens_per_s": res["tokens_per_s"]}}))
""")


def measured(devices=(1, 2, 4, 8)):
    rows = []
    for n in devices:
        script = _MEASURE.format(src=SRC, ndev=n)
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-2000:])
        rows.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    base = rows[0]["tokens_per_s"]
    for r in rows:
        r["speedup"] = round(r["tokens_per_s"] / base, 2)
        r["efficiency"] = round(r["speedup"] / r["ndev"], 2)
        r["note"] = ("placeholder devices share ONE physical core: "
                     "efficiency measures framework overhead, not hardware "
                     "scaling (see the modeled_v5e rows for the TPU story)")
    return rows


# -- analytic TPU model ------------------------------------------------------
PEAK = V5E["flops_bf16"]
HBM = V5E["hbm_bytes_s"]
BW = V5E["ici_link_bytes_s"]
ALPHA = 1e-6


def modeled_llama8b(unit_k: int = 1):
    """Step-time model for llama3-8b FSDP at growing DP degree, fixed global
    batch 1024 x 4k tokens (strong scaling)."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("llama3_8b")
    model = build_model(cfg)
    # shapes only: a concrete PRNG key would start a JAX backend in this
    # parent, which must stay off the devices its children use
    shapes = jax.eval_shape(model.init,
                            jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    n_params = sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes))
    stack = shapes["blocks"]
    layer_bytes = sum(math.prod(l.shape[1:]) * 2
                      for l in jax.tree_util.tree_leaves(stack))
    tokens_global = 1024 * 4096
    rows = []
    for dp in (16, 32, 64, 128, 256, 512, 1024):
        compute = 6 * n_params * tokens_global / dp / PEAK
        # fwd+bwd FSDP traffic: 2x all-gather + 1x reduce-scatter of params
        n_msgs = 3 * cfg.n_layers / unit_k
        msg = layer_bytes * unit_k / dp
        coll = n_msgs * (ALPHA * math.log2(dp) + msg / BW)
        mem = (18 * n_params / dp + 12 * tokens_global / dp * cfg.d_model *
               cfg.n_layers * 0.25) / HBM
        step = max(compute, coll, mem)
        rows.append({
            "dp": dp, "unit_k": unit_k,
            "compute_s": round(compute, 4),
            "collective_s": round(coll, 4),
            "memory_s": round(mem, 4),
            "step_bound": max(
                (("compute", compute), ("collective", coll), ("memory", mem)),
                key=lambda kv: kv[1])[0],
            "tokens_per_s_per_chip": int(tokens_global / dp / step),
            "ag_msg_MB": round(msg / 1e6, 3),
        })
    return rows


def run(fast: bool = False):
    out = {"modeled_llama8b_unit1": modeled_llama8b(1),
           "modeled_llama8b_unit8": modeled_llama8b(8)}
    if not fast:
        out["measured_cpu_ddp"] = measured()
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
