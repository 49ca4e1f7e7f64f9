"""Sharding plans — the paper's parallelization strategies as pluggable
components (FSDP / HSDP / TP / EP and their compositions).

A plan maps each param leaf's *logical axes* (from ``model.param_axes()``)
to mesh axes and yields NamedShardings. Divisibility failures fall back to
replication and are recorded (the IF-validation analog for sharding):
granite's MQA (kv=1) and whisper's 6 heads exercise this on a 16-way TP axis.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import base as B

# logical axes that Megatron-style TP shards over the model axis
TP_AXES = {B.HEADS, B.KV_HEADS, B.D_FF, B.VOCAB, B.D_INNER, B.CONV_DIM,
           B.D_EXPERT}


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """A composition of parallelization strategies."""

    name: str
    tp: bool = False                       # tensor parallelism over `model`
    fsdp_axes: Tuple[str, ...] = ()        # param shard axes (largest-dim rule)
    dp_axes: Tuple[str, ...] = ("data",)   # batch shard axes
    ep: bool = False                       # expert parallelism over `model`
    ep_storage_axes: Tuple[str, ...] = ()  # expert-weight storage sharding
    ep_axes: Tuple[str, ...] = ("model",)  # mesh axes the expert dim shards over
    pp: int = 1                            # pipeline stages over `pipe_axis`
    pipe_axis: str = "pipe"                # mesh axis the stage dim shards over
    n_micro: int = 0                       # microbatches (0 -> 2*pp default)

    def describe(self) -> str:
        parts = [f"dp={','.join(self.dp_axes)}"]
        if self.fsdp_axes:
            parts.append(f"fsdp={','.join(self.fsdp_axes)}")
        if self.tp:
            parts.append("tp=model")
        if self.ep:
            parts.append(
                "ep=" + ",".join(self.ep_axes)
                + (f"+storage={','.join(self.ep_storage_axes)}"
                   if self.ep_storage_axes else "")
            )
        if self.pp > 1:
            parts.append(f"pp={self.pp}@{self.pipe_axis}"
                         f"(m={self.n_micro or 2 * self.pp})")
        return f"{self.name}({'; '.join(parts)})"

    def effective_n_micro(self, global_batch: int = 0) -> int:
        """Microbatch count actually used by the schedule: ``n_micro`` (or
        the ``2*pp`` default) reduced to the largest divisor of the global
        batch so every microbatch is equal-sized."""
        from . import pipeline as PIPE

        return PIPE.effective_n_micro(self.n_micro, self.pp, global_batch)


def make_plan(name: str, multi_pod: bool = False) -> ShardingPlan:
    """The built-in strategy catalog (registered as components)."""
    pod = ("pod",) if multi_pod else ()
    dp = pod + ("data",)
    plans = {
        # pure data parallel: params replicated (paper's DDP baseline)
        "ddp": ShardingPlan("ddp", dp_axes=dp),
        # FSDP: fully shard params over ALL data axes (ZeRO-3)
        "fsdp": ShardingPlan("fsdp", fsdp_axes=dp, dp_axes=dp),
        # HSDP: shard within pod, replicate across pods (paper's hybrid)
        "hsdp": ShardingPlan("hsdp", fsdp_axes=("data",), dp_axes=dp),
        # 2D/3D: FSDP × TP
        "fsdp_tp": ShardingPlan("fsdp_tp", tp=True, fsdp_axes=dp, dp_axes=dp),
        "hsdp_tp": ShardingPlan("hsdp_tp", tp=True, fsdp_axes=("data",), dp_axes=dp),
        # MoE: FSDP × TP × EP (experts over model, storage over data)
        "fsdp_tp_ep": ShardingPlan(
            "fsdp_tp_ep", tp=True, fsdp_axes=dp, dp_axes=dp, ep=True,
            ep_storage_axes=("data",),
        ),
        "hsdp_tp_ep": ShardingPlan(
            "hsdp_tp_ep", tp=True, fsdp_axes=("data",), dp_axes=dp, ep=True,
            ep_storage_axes=("data",),
        ),
        # serving plan: no FSDP (no optimizer state at inference) — experts
        # sharded over EVERY chip (EP degree = data x model), dense/attention
        # TP over model. Kills the per-step expert-weight all-gathers that
        # dominate MoE decode under the training plan.
        "serve_ep": ShardingPlan(
            "serve_ep", tp=True, fsdp_axes=(), dp_axes=("data",), ep=True,
            ep_storage_axes=(), ep_axes=pod + ("data", "model"),
        ),
        # 3D: pipeline stages x FSDP (x TP x EP). The stage dim rides the
        # `pipe` mesh axis; FSDP/TP shard each stage's slice as usual.
        "pp2_fsdp": ShardingPlan("pp2_fsdp", fsdp_axes=dp, dp_axes=dp, pp=2),
        "pp2_fsdp_tp": ShardingPlan(
            "pp2_fsdp_tp", tp=True, fsdp_axes=dp, dp_axes=dp, pp=2),
        "pp2_fsdp_tp_ep": ShardingPlan(
            "pp2_fsdp_tp_ep", tp=True, fsdp_axes=dp, dp_axes=dp, ep=True,
            ep_storage_axes=("data",), pp=2,
        ),
    }
    if name not in plans:
        raise ValueError(f"unknown plan {name!r}; available: {sorted(plans)}")
    return plans[name]


_PLAN_FIELDS = {f.name: f for f in dataclasses.fields(ShardingPlan)}
_AXIS_FIELDS = {"fsdp_axes", "dp_axes", "ep_storage_axes", "ep_axes"}


def custom_plan(spec: Dict[str, Any]) -> ShardingPlan:
    """Build a validated :class:`ShardingPlan` from a field mapping — the
    declarative `plan: {tp: true, pp: 2, ...}` form in run YAML.  A bare
    string is a catalog lookup, so sweeps can grid over both forms."""
    if isinstance(spec, str):
        return make_plan(spec)
    if isinstance(spec, ShardingPlan):
        return spec
    if not isinstance(spec, dict):
        raise ValueError(f"plan spec must be a name or mapping, got {type(spec)}")
    kw: Dict[str, Any] = dict(spec)
    unknown = set(kw) - set(_PLAN_FIELDS)
    if unknown:
        raise ValueError(
            f"unknown plan field(s) {sorted(unknown)}; valid: "
            f"{sorted(_PLAN_FIELDS)}")
    for k in _AXIS_FIELDS & set(kw):
        v = kw[k]
        if isinstance(v, str):
            v = (v,)
        if not (isinstance(v, (list, tuple))
                and all(isinstance(a, str) for a in v)):
            raise ValueError(f"plan.{k} must be a list of mesh-axis names, "
                             f"got {kw[k]!r}")
        kw[k] = tuple(v)
    for k in ("tp", "ep"):
        if k in kw and not isinstance(kw[k], bool):
            raise ValueError(f"plan.{k} must be a bool, got {kw[k]!r}")
    for k in ("pp", "n_micro"):
        if k in kw:
            if not isinstance(kw[k], int) or isinstance(kw[k], bool) or kw[k] < 0:
                raise ValueError(f"plan.{k} must be a non-negative int, "
                                 f"got {kw[k]!r}")
    if kw.get("pp", 1) < 1:
        raise ValueError("plan.pp must be >= 1")
    if "pipe_axis" in kw and not isinstance(kw["pipe_axis"], str):
        raise ValueError(f"plan.pipe_axis must be a str, got {kw['pipe_axis']!r}")
    kw.setdefault("name", "custom")
    plan = ShardingPlan(**kw)
    if plan.pp > 1 and plan.pipe_axis in plan.dp_axes + plan.fsdp_axes:
        raise ValueError(
            f"plan.pipe_axis {plan.pipe_axis!r} collides with dp/fsdp axes")
    return plan


def default_plan_for(cfg: B.ArchConfig, multi_pod: bool = False) -> ShardingPlan:
    if cfg.arch_type == "moe":
        return make_plan("fsdp_tp_ep" if not multi_pod else "hsdp_tp_ep", multi_pod)
    return make_plan("fsdp_tp" if not multi_pod else "hsdp_tp", multi_pod)


# ---------------------------------------------------------------------------
# param specs
# ---------------------------------------------------------------------------
def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _norm_axes(axes: Tuple[str, ...]):
    """Singleton axis tuples become bare names: newer PartitionSpec no longer
    normalizes ("data",) -> "data" itself, and the two spell the same
    sharding."""
    return axes if len(axes) > 1 else axes[0]


def leaf_spec(plan: ShardingPlan, mesh: Mesh, shape: Tuple[int, ...],
              logical: Tuple[Any, ...], warnings: Optional[List[str]] = None,
              path: str = "") -> P:
    assert len(shape) == len(logical), f"{path}: {shape} vs {logical}"
    spec: List[Any] = [None] * len(shape)
    tp_size = mesh.shape.get("model", 1)

    # pipeline stages: the stacked LAYER dim is split into `pp` contiguous
    # chunks over the pipe axis — per device this IS the [S, L/S, ...]
    # staged layout, while the stored tree keeps its plan-independent
    # [L, ...] shape (elastic restore needs no reshape across plans)
    if plan.pp > 1 and plan.pipe_axis in mesh.shape and B.LAYER in logical:
        l_dim = logical.index(B.LAYER)
        pp_size = mesh.shape[plan.pipe_axis]
        if shape[l_dim] % pp_size == 0 and shape[l_dim] >= pp_size:
            spec[l_dim] = plan.pipe_axis
        elif warnings is not None:
            warnings.append(
                f"{path}: layers {shape[l_dim]} !% pp {pp_size} -> unstaged")

    is_expert = B.EXPERTS in logical
    if plan.ep and is_expert:
        e_dim = logical.index(B.EXPERTS)
        ep_size = _axes_size(mesh, plan.ep_axes)
        if shape[e_dim] % ep_size == 0:
            spec[e_dim] = _norm_axes(plan.ep_axes)
        elif warnings is not None:
            warnings.append(f"{path}: experts {shape[e_dim]} !% ep {ep_size}")
        if plan.ep_storage_axes and B.D_MODEL in logical:
            d_dim = logical.index(B.D_MODEL)
            if shape[d_dim] % _axes_size(mesh, plan.ep_storage_axes) == 0:
                spec[d_dim] = _norm_axes(plan.ep_storage_axes)
        return P(*spec)

    if plan.tp:
        for i, (n, ax) in enumerate(zip(shape, logical)):
            if ax in TP_AXES:
                if n % tp_size == 0:
                    spec[i] = "model"
                    break  # one TP axis per tensor
                elif warnings is not None:
                    warnings.append(f"{path}: {ax}={n} !% model {tp_size} -> replicated")

    if plan.fsdp_axes:
        fs = _axes_size(mesh, plan.fsdp_axes)
        # largest unassigned, non-layer dim divisible by the fsdp extent
        cands = [
            (n, i)
            for i, (n, ax) in enumerate(zip(shape, logical))
            if spec[i] is None and ax is not B.LAYER and n % fs == 0 and n >= fs
        ]
        if cands:
            _, i = max(cands)
            spec[i] = _norm_axes(plan.fsdp_axes)
        elif warnings is not None and max(shape, default=0) > 1024:
            warnings.append(f"{path}: no dim divisible by fsdp {fs} in {shape}")
    return P(*spec)


def param_shardings(plan: ShardingPlan, mesh: Mesh, param_shapes,
                    param_axes) -> Tuple[Any, List[str]]:
    """Pytree of NamedShardings for the param tree + divisibility warnings."""
    warnings: List[str] = []
    paths_shapes = jax.tree_util.tree_flatten_with_path(param_shapes)[0]
    flat_axes = jax.tree_util.tree_flatten(
        param_axes, is_leaf=lambda t: isinstance(t, tuple)
    )[0]
    assert len(paths_shapes) == len(flat_axes), (
        f"param/axes tree mismatch: {len(paths_shapes)} vs {len(flat_axes)}"
    )
    specs = []
    for (path, leaf), logical in zip(paths_shapes, flat_axes):
        pstr = jax.tree_util.keystr(path)
        specs.append(
            NamedSharding(
                mesh, leaf_spec(plan, mesh, tuple(leaf.shape), logical, warnings, pstr)
            )
        )
    treedef = jax.tree_util.tree_structure(param_shapes)
    return jax.tree_util.tree_unflatten(treedef, specs), warnings


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------
def batch_shardings(plan: ShardingPlan, mesh: Mesh, batch_shapes) -> Any:
    dp = plan.dp_axes
    dp_size = _axes_size(mesh, dp)

    def spec(path, leaf):
        bdim = leaf.shape[0] if leaf.shape else 0
        s: List[Any] = [None] * len(leaf.shape)
        if bdim and bdim % dp_size == 0:
            s[0] = dp
        elif len(leaf.shape) >= 2:
            # batch too small (long-context decode): shard the sequence dim
            if leaf.shape[1] % mesh.shape.get("data", 1) == 0 and leaf.shape[1] > 1:
                s[1] = "data"
        return NamedSharding(mesh, P(*s))

    return jax.tree_util.tree_map_with_path(spec, batch_shapes)


def cache_shardings(plan: ShardingPlan, mesh: Mesh, cache_shapes,
                    batch_size: int) -> Any:
    """KV/state cache: batch over dp if divisible; else seq over data.
    KV-head dim over model when divisible, else seq over model (MQA/MLA)."""
    dp = plan.dp_axes
    dp_size = _axes_size(mesh, dp)
    tp = mesh.shape.get("model", 1)
    data = mesh.shape.get("data", 1)

    def spec(path, leaf):
        shape = leaf.shape
        s: List[Any] = [None] * len(shape)
        # leading dim is layers (stacked caches): [L, B, ...]
        b_dim = 1 if len(shape) >= 2 else 0
        batch_ok = shape[b_dim] % dp_size == 0
        if batch_ok:
            s[b_dim] = dp
        name = jax.tree_util.keystr(path)
        if "conv" in name:  # [L, B, W-1, conv_dim]
            if shape[-1] % tp == 0:
                s[-1] = "model"
            return NamedSharding(mesh, P(*s))
        if "ssm" in name:   # [L, B, H, P, N]
            if len(shape) >= 3 and shape[2] % tp == 0:
                s[2] = "model"
            return NamedSharding(mesh, P(*s))
        # attention caches: [L, B, S, K, dh], paged GQA [L, n_blocks, bl,
        # K * dh] (heads-major features) or MLA [L, B, S, r]
        seq_dim = 2 if len(shape) >= 3 else None
        paged_kv = len(shape) == 4 and name.endswith(("['k']", "['v']"))
        kv_dim = 3 if len(shape) >= 5 or paged_kv else None
        if kv_dim is not None and shape[kv_dim] % tp == 0:
            s[kv_dim] = "model"
        elif seq_dim is not None and shape[seq_dim] % tp == 0:
            s[seq_dim] = "model"
        if not batch_ok and seq_dim is not None:
            cur = s[seq_dim]
            if shape[seq_dim] % (data * (tp if cur == "model" else 1)) == 0:
                s[seq_dim] = ("data", "model") if cur == "model" else "data"
        return NamedSharding(mesh, P(*s))

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


# ---------------------------------------------------------------------------
# spec serialization (checkpoint manifests record every leaf's layout)
# ---------------------------------------------------------------------------
def spec_to_json(spec) -> List[Any]:
    """PartitionSpec -> JSON-able list: each entry None | axis | [axes...]."""
    out: List[Any] = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            out.append([str(a) for a in entry])
        else:
            out.append(str(entry))
    return out


def spec_from_json(obj: Optional[List[Any]]) -> P:
    """The inverse of :func:`spec_to_json` (None -> fully replicated)."""
    if not obj:
        return P()
    entries = [tuple(e) if isinstance(e, list) else e for e in obj]
    return P(*entries)


# ---------------------------------------------------------------------------
# full-train-state shardings (the gym's layout; elastic restore re-derives
# the same pytree for a DIFFERENT plan/mesh than a checkpoint was saved on)
# ---------------------------------------------------------------------------
def train_state_shardings(plan: ShardingPlan, mesh: Mesh, model,
                          optimizer, seed: int = 0) -> Tuple[Any, List[str]]:
    """``({"params", "opt", "step"} sharding pytree, warnings)``."""
    from ..train import steps as ST

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    pspecs, warnings = param_shardings(plan, mesh, pshapes, model.param_axes())
    rep = NamedSharding(mesh, P())
    opt_shapes = jax.eval_shape(optimizer.init, pshapes)
    return {
        "params": pspecs,
        "opt": ST.opt_state_shardings(opt_shapes, pspecs, rep),
        "step": rep,
    }, warnings


def mesh_context(plan: ShardingPlan, mesh: Mesh) -> B.MeshContext:
    # pipeline is active only when the mesh actually carries the pipe axis
    # (a pp plan on a data x model mesh degrades to its unpipelined core,
    # matching how TP/EP degrade on a 1-wide model axis)
    pp = 1
    if plan.pp > 1 and plan.pipe_axis in mesh.shape:
        pp = mesh.shape[plan.pipe_axis]
        if pp != plan.pp:
            raise ValueError(
                f"plan {plan.name!r} wants pp={plan.pp} but mesh axis "
                f"{plan.pipe_axis!r} has {pp} devices")
    return B.MeshContext(
        mesh=mesh,
        dp_axes=plan.dp_axes,
        tp_axis="model" if (plan.tp or plan.ep) else None,
        ep_enabled=plan.ep,
        ep_axes=plan.ep_axes,
        pp=pp,
        pipe_axis=plan.pipe_axis if pp > 1 else None,
        n_micro=plan.n_micro,
    )


def pipeline_info(plan: ShardingPlan, mesh: Optional[Mesh] = None,
                  global_batch: int = 0) -> Dict[str, Any]:
    """Analytic pipeline telemetry for results/BENCH rows: stage count,
    effective microbatches, and the GPipe bubble fraction."""
    from . import pipeline as PIPE

    pp = plan.pp
    if mesh is None or plan.pipe_axis not in mesh.shape:
        pp = 1
    m = plan.effective_n_micro(global_batch) if pp > 1 else 1
    return {
        "pp": pp,
        "pipe_axis": plan.pipe_axis if pp > 1 else None,
        "n_micro": m,
        "bubble_fraction": PIPE.bubble_fraction(pp, m),
    }
