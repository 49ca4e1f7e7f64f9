"""Built-in component catalog: registers every pluggable component with the
default registry. Importing this module populates the registry; custom
components can be added at runtime with the same API (no framework changes)."""
from __future__ import annotations

import json
from typing import Any, Dict, Optional

from ..ckpt import AsyncCheckpointer, RetentionPolicy
from ..config.registry import DEFAULT_REGISTRY as REG
from ..configs import ARCH_IDS, get_config, get_reduced, reduce_config
from ..configs.shapes import SHAPES, InputShape
from ..data.packed_dataset import ChunkedLMDataset, PackedDataset, ShardedLoader, synthetic_dataset
from ..data.prefetch import PrefetchLoader
from ..data.tokenizer import BpeTokenizer, ByteTokenizer
from ..launch import mesh as MESH
from ..launch.specs import PrecisionPolicy
from ..models import build_model
from ..models.base import ArchConfig, MLAConfig, MoEConfig, SSMConfig, Model
from ..models.stacked import REMAT_VARIANTS, RematPolicy
from ..optim import schedules as SCHED
from ..optim.adamw import AdamW
from ..sharding.plans import ShardingPlan, custom_plan, make_plan
from . import interfaces as IF
from .gym import Gym

IF.register_builtin_interfaces()

# virtual-subclass the concrete builtins into their IFs
IF.OptimizerIF.register(AdamW)
IF.TokenizerIF.register(ByteTokenizer)
IF.TokenizerIF.register(BpeTokenizer)
IF.DatasetIF.register(ChunkedLMDataset)
from ..posttrain.dpo import PreferencePairDataset  # noqa: E402
from ..posttrain.lora import FrozenBaseOptimizer  # noqa: E402
from ..posttrain.sft import PackedSFTDataset  # noqa: E402

IF.DatasetIF.register(PackedSFTDataset)
IF.DatasetIF.register(PreferencePairDataset)
IF.OptimizerIF.register(FrozenBaseOptimizer)
IF.LoaderIF.register(ShardedLoader)
IF.LoaderIF.register(PrefetchLoader)
IF.MeshProviderIF.register(MESH.MeshProvider)
IF.CheckpointerIF.register(AsyncCheckpointer)

_REGISTERED = False


def _reg(component_key: str, variant_key: str, factory, interface=None):
    REG.register(component_key, variant_key, factory, interface)


def register_all() -> None:
    global _REGISTERED
    if _REGISTERED:
        return
    _REGISTERED = True

    # -- arch configs -------------------------------------------------------
    for arch in ARCH_IDS + ["llama3_8b"]:
        _reg("arch_config", arch,
             (lambda a: (lambda reduced=False, **overrides: _cfg(a, reduced, overrides)))(arch),
             ArchConfig)
    _reg("arch_config", "custom", _custom_cfg, ArchConfig)

    # -- models -------------------------------------------------------------
    _reg("model", "auto", lambda arch_config: build_model(arch_config), Model)

    # -- optimizers / schedules ----------------------------------------------
    _reg("optimizer", "adamw",
         lambda lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                grad_clip=1.0:
         AdamW(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
               grad_clip=grad_clip),
         IF.OptimizerIF)
    _reg("lr_schedule", "constant", SCHED.constant)
    _reg("lr_schedule", "warmup_cosine", SCHED.warmup_cosine)
    _reg("lr_schedule", "wsd", SCHED.wsd)

    # -- sharding plans -------------------------------------------------------
    for name in ("ddp", "fsdp", "hsdp", "fsdp_tp", "hsdp_tp", "fsdp_tp_ep",
                 "hsdp_tp_ep", "serve_ep", "pp2_fsdp", "pp2_fsdp_tp",
                 "pp2_fsdp_tp_ep"):
        _reg("sharding_plan", name,
             (lambda n: (lambda multi_pod=False: make_plan(n, multi_pod)))(name),
             ShardingPlan)
    # declarative custom plans: validated ShardingPlan fields straight from
    # YAML (`plan: {tp: true, pp: 2, ...}`), so sweeps can grid over novel
    # compositions without touching the catalog
    _reg("sharding_plan", "custom", lambda **kw: custom_plan(kw), ShardingPlan)

    # -- meshes ----------------------------------------------------------------
    # Every variant returns a MeshProvider (build() -> mesh, lazily) — no more
    # factories returning bare lambdas that consumers must callable()-sniff.
    _reg("mesh_provider", "single_device", MESH.SingleDeviceMesh,
         IF.MeshProviderIF)
    _reg("mesh_provider", "local", MESH.LocalMesh)
    _reg("mesh_provider", "production", MESH.ProductionMesh)
    _reg("mesh_provider", "split", MESH.SplitMesh)

    # -- input shapes ----------------------------------------------------------
    for name in SHAPES:
        _reg("shape", name, (lambda n: (lambda: SHAPES[n]))(name), InputShape)
    _reg("shape", "custom", _custom_shape, InputShape)

    # -- precision policies ----------------------------------------------------
    _reg("precision", "policy",
         lambda bf16_params=False, serve_bf16=False:
         PrecisionPolicy(bf16_params=bf16_params, serve_bf16=serve_bf16),
         PrecisionPolicy)

    # -- tokenizers -----------------------------------------------------------
    _reg("tokenizer", "byte", ByteTokenizer, IF.TokenizerIF)
    _reg("tokenizer", "bpe", _bpe_tokenizer, IF.TokenizerIF)

    # -- datasets / loaders ----------------------------------------------------
    _reg("dataset", "packed_chunked",
         lambda prefix, seq_len, seed=0, shuffle=True:
         ChunkedLMDataset(PackedDataset(prefix), seq_len, seed, shuffle),
         IF.DatasetIF)
    _reg("dataset", "synthetic",
         _synthetic_chunked,
         IF.DatasetIF)
    # post-training datasets (loss-masked SFT rows, DPO preference pairs)
    from ..posttrain.dpo import preference_synthetic_dataset
    from ..posttrain.sft import sft_jsonl_dataset, sft_synthetic_dataset

    _reg("dataset", "sft_synthetic", sft_synthetic_dataset, IF.DatasetIF)
    _reg("dataset", "sft_jsonl", sft_jsonl_dataset, IF.DatasetIF)
    _reg("dataset", "preference_synthetic", preference_synthetic_dataset,
         IF.DatasetIF)
    _reg("loader", "sharded",
         lambda dataset, global_batch, dp_rank=0, dp_size=1:
         ShardedLoader(dataset, global_batch, dp_rank, dp_size),
         IF.LoaderIF)
    _reg("loader", "prefetch",
         lambda loader, depth=2, to_device=True:
         PrefetchLoader(loader, depth=depth, to_device=to_device),
         IF.LoaderIF)

    # -- remat policies (scan-over-layers activation checkpointing) ----------
    for name in REMAT_VARIANTS:
        _reg("remat_policy", name,
             (lambda n: (lambda: RematPolicy(n)))(name), RematPolicy)

    # -- evaluators ---------------------------------------------------------------
    from .evaluator import PerplexityEvaluator

    _reg("evaluator", "perplexity",
         lambda dataset, n_samples=16, offset=None, batch=4:
         PerplexityEvaluator(dataset, n_samples, offset, batch))

    # -- checkpointers (elastic checkpoint subsystem, repro.ckpt) -----------
    _reg("checkpointer", "async",
         lambda ckpt_dir, keep_last=3, keep_every=0:
         AsyncCheckpointer(ckpt_dir,
                           RetentionPolicy(int(keep_last), int(keep_every))),
         IF.CheckpointerIF)
    _reg("checkpointer", "sync",
         lambda ckpt_dir, keep_last=3, keep_every=0:
         AsyncCheckpointer(ckpt_dir,
                           RetentionPolicy(int(keep_last), int(keep_every)),
                           background=False),
         IF.CheckpointerIF)

    # -- resilience (repro.resilience) -------------------------------------
    from ..resilience import FaultInjector

    _reg("fault_injector", "schedule",
         lambda faults=(): FaultInjector.from_config(faults),
         FaultInjector)

    # -- trackers ---------------------------------------------------------------
    _reg("tracker", "stdout", lambda prefix="": _StdoutTracker(prefix),
         IF.TrackerIF)
    _reg("tracker", "jsonl", lambda path: _JsonlTracker(path), IF.TrackerIF)

    # -- telemetry sinks (repro.telemetry) ----------------------------------
    from ..telemetry.sinks import (CsvSink, JsonlSink, ListSink, MultiSink,
                                   StdoutSink, TelemetrySink)

    _reg("sink", "jsonl", lambda path: JsonlSink(path), TelemetrySink)
    _reg("sink", "csv", lambda path: CsvSink(path), TelemetrySink)
    _reg("sink", "stdout", lambda prefix="telemetry ": StdoutSink(prefix),
         TelemetrySink)
    _reg("sink", "memory", lambda: ListSink(), TelemetrySink)
    _reg("sink", "multi", lambda sinks: MultiSink(list(sinks)), TelemetrySink)

    # -- gym ---------------------------------------------------------------------
    _reg("gym", "standard",
         lambda model, optimizer, loader, mesh_provider=None, sharding_plan=None,
                seed=0, grad_accum=1, log_every=10, eval_every=0, ckpt_every=0,
                ckpt_dir="", checkpointer=None, prefetch=2, tracker=None:
         Gym(model=model, optimizer=optimizer, loader=loader,
             mesh=_build_mesh(mesh_provider),
             plan=sharding_plan, seed=seed, grad_accum=grad_accum,
             log_every=log_every, eval_every=eval_every, ckpt_every=ckpt_every,
             ckpt_dir=ckpt_dir or getattr(checkpointer, "ckpt_dir", ""),
             checkpointer=checkpointer, prefetch=prefetch, logger=tracker),
         Gym)


# ---------------------------------------------------------------------------
def _cfg(arch: str, reduced: bool, overrides: Dict[str, Any]) -> ArchConfig:
    cfg = get_reduced(arch) if reduced else get_config(arch)
    return cfg.with_(**overrides) if overrides else cfg


def _custom_cfg(**kw) -> ArchConfig:
    if isinstance(kw.get("moe"), dict):
        kw["moe"] = MoEConfig(**kw["moe"])
    if isinstance(kw.get("mla"), dict):
        kw["mla"] = MLAConfig(**kw["mla"])
    if isinstance(kw.get("ssm"), dict):
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ArchConfig(**kw)


def _build_mesh(mesh_provider):
    """``mesh_provider`` components are MeshProvider objects; a raw mesh (or
    None) passes through for direct Gym construction."""
    if mesh_provider is None:
        return None
    build = getattr(mesh_provider, "build", None)
    if callable(build):
        return build()
    return mesh_provider


def _custom_shape(seq_len: int, global_batch: int, kind: str,
                  name: str = "custom") -> InputShape:
    if kind not in ("train", "prefill", "decode"):
        raise ValueError(f"shape kind must be train|prefill|decode, got {kind!r}")
    return InputShape(name, int(seq_len), int(global_batch), kind)


def _bpe_tokenizer(path: str = "", corpus: str = "",
                   n_merges: Optional[int] = None) -> BpeTokenizer:
    """Load from ``path``, or train ``n_merges`` merges on a ``corpus`` text
    file.  ``n_merges`` without a corpus is a misconfiguration — it used to be
    silently ignored."""
    if path:
        if n_merges is not None:
            raise ValueError(
                "tokenizer/bpe: n_merges applies when training from 'corpus'; "
                "a tokenizer loaded from 'path' has its merges baked in"
            )
        return BpeTokenizer.load(path)
    if corpus:
        with open(corpus) as f:
            texts = f.read().splitlines()
        return BpeTokenizer.train(texts, n_merges=256 if n_merges is None
                                  else int(n_merges))
    if n_merges is not None:
        raise ValueError(
            "tokenizer/bpe: n_merges needs a 'corpus' text file to train on"
        )
    return BpeTokenizer()


def _synthetic_chunked(n_tokens: int, vocab: int, prefix: str, seq_len: int,
                       seed: int = 0, shuffle: bool = True):
    """Seeded synthetic tokens under ``prefix``, generated once. The files
    are keyed by everything that generates them, so a run never reads data
    made for another vocabulary, size or seed at the same prefix."""
    import os

    from ..data.packed_dataset import DOCIDX_SUFFIX

    keyed = f"{prefix}.v{int(vocab)}.n{int(n_tokens)}.s{int(seed)}"
    if not os.path.exists(keyed + DOCIDX_SUFFIX):   # written last
        os.makedirs(os.path.dirname(keyed) or ".", exist_ok=True)
        synthetic_dataset(n_tokens, vocab, keyed, seed)
    return ChunkedLMDataset(PackedDataset(keyed), seq_len, seed, shuffle)


class _StdoutTracker(IF.TrackerIF):
    def __init__(self, prefix: str = ""):
        self.prefix = prefix

    def __call__(self, metrics: Dict[str, Any]) -> None:
        print(self.prefix + json.dumps(metrics, default=float), flush=True)


class _JsonlTracker(IF.TrackerIF):
    def __init__(self, path: str):
        self.path = path

    def __call__(self, metrics: Dict[str, Any]) -> None:
        with open(self.path, "a") as f:
            f.write(json.dumps(metrics, default=float) + "\n")


register_all()
