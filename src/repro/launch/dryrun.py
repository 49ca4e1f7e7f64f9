import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination on placeholder devices; emit memory / cost / collective analysis
for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Run API (preferred — every knob is a YAML-addressable component):

  PYTHONPATH=src python -m repro dryrun --config examples/configs/dryrun.yaml

Deprecated flag shim (delegates through the same Run API):

  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
      --shape train_4k [--multi-pod] [--plan fsdp_tp] [--json out.json]
"""
import argparse
import json
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..configs.shapes import SHAPES
from ..models import build_model
from ..optim.adamw import AdamW
from ..sharding import plans as PL
from ..telemetry.accounting import V5E
from ..train import steps as ST
from . import mesh as MESH
from . import specs as SP


from .hlo_analysis import analyze as analyze_hlo


def model_flops(cfg, shape) -> float:
    """6·N_active·D (training) or 2·N_active·D (per-token inference).

    The estimate lives in :mod:`repro.telemetry.accounting` so dryrun's
    roofline and the live MFU accounting share one numerator; this alias
    keeps the historic dryrun import path working.
    """
    from ..telemetry.accounting import model_flops as _mf

    return _mf(cfg, shape)


# ---------------------------------------------------------------------------
# the dry run
# ---------------------------------------------------------------------------
def dryrun(arch: str, shape_name: str, multi_pod: bool = False,
           plan_name: str = "", scan_block: int = 0,
           verbose: bool = True, mesh_split: str = "",
           mla_absorb: bool = False, grad_accum: int = 1,
           serve_bf16: bool = False, bf16_params: bool = False,
           keep_messages: bool = False) -> Dict[str, Any]:
    """Historic flag-based entrypoint, now a thin wrapper over the
    component-driven :func:`compile_run` core."""
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if scan_block:
        cfg = cfg.with_(scan_block_size=scan_block)
    if mla_absorb:
        cfg = cfg.with_(mla_absorb=True)
    if mesh_split:  # e.g. "32x8": re-split the same 256 chips (perf tuning)
        dp, tp = (int(x) for x in mesh_split.split("x"))
        assert dp * tp == 256 and not multi_pod
        mesh = MESH.SplitMesh(dp, tp)
    else:
        mesh = MESH.ProductionMesh(multi_pod=multi_pod)
    plan = PL.make_plan(plan_name, multi_pod) if plan_name else None
    return compile_run(cfg, shape, mesh, plan, grad_accum=grad_accum,
                       serve_bf16=serve_bf16, bf16_params=bf16_params,
                       verbose=verbose, keep_messages=keep_messages,
                       arch_label=arch, shape_label=shape_name)


def compile_run(cfg, shape, mesh, plan=None, *, grad_accum: int = 1,
                bf16_params: bool = False, serve_bf16: bool = False,
                verbose: bool = False, keep_messages: bool = False,
                arch_label: str = "", shape_label: str = "") -> Dict[str, Any]:
    """Lower + compile one (arch config × shape × mesh × plan) point and emit
    the memory / cost / collective analysis.

    Every argument is a resolved component (the Run API's ``dryrun`` graph):
    ``cfg`` an ArchConfig, ``shape`` an InputShape, ``mesh`` a jax Mesh or a
    MeshProvider (built lazily, after the skip check), ``plan`` a
    ShardingPlan (default: per-arch), precision via the two bf16 flags.
    """
    arch_label = arch_label or cfg.name
    shape_label = shape_label or shape.name
    cfg = SP.adapt_config(cfg, shape)
    ok, why = SP.supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch_label, "shape": shape_label, "skipped": why}

    if hasattr(mesh, "build"):  # MeshProvider — build only past the skip check
        mesh = mesh.build()
    if mesh is None:
        raise ValueError("compile_run needs a mesh (a MeshProvider that "
                         "produces none cannot be dry-run)")
    multi_pod = "pod" in mesh.axis_names
    if plan is None:
        plan = PL.default_plan_for(cfg, multi_pod)
    mesh_ctx = PL.mesh_context(plan, mesh)
    storage_axes = plan.ep_storage_axes if plan.ep else ()
    model = build_model(cfg)

    t0 = time.time()
    if shape.kind == "train":
        opt = AdamW(lr=3e-4, master_weights=bf16_params)
        state_shapes = ST.abstract_train_state(
            model, opt, param_dtype=jnp.bfloat16 if bf16_params else None)
        pspecs, warnings = PL.param_shardings(
            plan, mesh, state_shapes["params"], model.param_axes()
        )
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        state_sh = {
            "params": pspecs,
            "opt": ST.opt_state_shardings(state_shapes["opt"], pspecs, rep),
            "step": rep,
        }
        ins = SP.input_specs(cfg, shape)
        batch_sh = PL.batch_shardings(plan, mesh, ins["batch"])
        step_fn = ST.make_train_step(model, opt, mesh_ctx, storage_axes,
                                     grad_accum=grad_accum)
        jitted = jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_shapes, ins["batch"])
    elif shape.kind == "prefill":
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs, warnings = PL.param_shardings(plan, mesh, pshapes, model.param_axes())
        ins = SP.input_specs(cfg, shape)
        batch_sh = PL.batch_shardings(plan, mesh, ins["batch"])
        step_fn = ST.make_prefill_step(model, mesh_ctx, storage_axes)
        jitted = jax.jit(step_fn, in_shardings=(pspecs, batch_sh))
        with mesh:
            lowered = jitted.lower(pshapes, ins["batch"])
    else:  # decode
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        if serve_bf16:  # serving keeps weights in bf16 (no f32 master needed)
            pshapes = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
                if l.dtype == jnp.float32 else l, pshapes)
        pspecs, warnings = PL.param_shardings(plan, mesh, pshapes, model.param_axes())
        ins = SP.input_specs(cfg, shape, model=model)
        cache_sh = PL.cache_shardings(plan, mesh, ins["cache"], shape.global_batch)
        tok_sh = PL.batch_shardings(
            plan, mesh, {"tokens": ins["tokens"], "positions": ins["positions"]}
        )
        step_fn = ST.make_serve_step(model, mesh_ctx)
        jitted = jax.jit(
            step_fn,
            in_shardings=(pspecs, cache_sh, tok_sh["tokens"], tok_sh["positions"]),
            donate_argnums=(1,),
        )
        with mesh:
            lowered = jitted.lower(pshapes, ins["cache"], ins["tokens"],
                                   ins["positions"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    hlo = compiled.as_text()
    ana = analyze_hlo(hlo)
    mflops, n_total, n_active = model_flops(cfg, shape)

    chips = mesh.devices.size
    flops_dev = float(ana["flops"])
    bytes_dev = float(ana["bytes"])
    res = {
        "arch": arch_label,
        "shape": shape_label,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "plan": plan.describe(),
        "chips": int(chips),
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "collective_bytes_per_dev": ana["collective_bytes"],
        "collective_counts": ana["collective_counts"],
        "collective_per_kind": ana["collective_per_kind"],
        "collective_msgs_large": sorted(
            ana["messages"], key=lambda m: -m[1]
        )[:8],
        "xla_cost_flops_unscaled": float(cost.get("flops", 0.0)),
        "model_flops_global": mflops,
        "n_params": n_total,
        "n_params_active": n_active,
        "compute_term_s": flops_dev / V5E["flops_bf16"],
        "memory_term_s": bytes_dev / V5E["hbm_bytes_s"],
        "collective_term_s": ana["collective_bytes"] / V5E["ici_link_bytes_s"],
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "sharding_warnings": warnings,
        # per-plan pipeline cost block (MoFa-style observable bubble term;
        # the `tune` run kind calibrates against these + collective bytes)
        "pipeline": PL.pipeline_info(plan, mesh, shape.global_batch
                                     if shape.kind == "train" else 0),
    }
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            try:
                res[f"mem_{attr}"] = int(getattr(mem, attr))
            except Exception:
                pass
    terms = {
        "compute": res["compute_term_s"],
        "memory": res["memory_term_s"],
        "collective": res["collective_term_s"],
    }
    res["dominant_term"] = max(terms, key=terms.get)
    res["useful_flops_ratio"] = (
        mflops / (flops_dev * chips) if flops_dev else 0.0
    )
    if verbose:
        print(json.dumps(res, indent=2, default=str))
        if mem is not None:
            print("memory_analysis:", mem)
    if keep_messages:
        res["messages"] = ana["messages"]
    return res


def main():
    """DEPRECATED shim: delegates to ``python -m repro dryrun``."""
    import warnings

    warnings.warn(
        "python -m repro.launch.dryrun is deprecated; use "
        "`python -m repro dryrun --config <run.yaml>` (this shim delegates "
        "through the same Run API)", DeprecationWarning, stacklevel=2)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--plan", default="")
    ap.add_argument("--scan-block", type=int, default=0)
    ap.add_argument("--mesh-split", default="")
    ap.add_argument("--mla-absorb", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--serve-bf16", action="store_true")
    ap.add_argument("--bf16-params", action="store_true")
    ap.add_argument("--json", default="")
    args = ap.parse_args()

    from ..run import api as run_api
    from ..run.legacy import legacy_dryrun_doc

    doc = legacy_dryrun_doc({
        "arch": args.arch, "shape": args.shape, "multi_pod": args.multi_pod,
        "plan_name": args.plan, "scan_block": args.scan_block,
        "mesh_split": args.mesh_split, "mla_absorb": args.mla_absorb,
        "grad_accum": args.grad_accum, "serve_bf16": args.serve_bf16,
        "bf16_params": args.bf16_params,
    }, name=f"dryrun_{args.arch}_{args.shape}".replace("/", "-"))
    res = run_api.execute_doc(doc, options={"verbose": True},
                              log=lambda m: print(m, flush=True))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2, default=str)
    return 0 if ("skipped" in res or res.get("chips")) else 1


if __name__ == "__main__":
    sys.exit(main())
