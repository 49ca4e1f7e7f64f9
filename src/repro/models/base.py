"""Model IF and the unified architecture config.

Models are pure-functional JAX: ``init`` builds a params pytree, ``apply``
computes logits, ``decode_*`` implement single-token serving with a KV/state
cache. Every param leaf carries *logical axis names* (via ``param_axes``)
that sharding plans map onto mesh axes — the JAX analog of Modalities'
IF-level decoupling between model code and parallelization strategy.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Logical axis names (sharding plans map these to mesh axes)
# ---------------------------------------------------------------------------
LAYER = "layer"          # stacked-layer dim (never sharded; scan dim)
VOCAB = "vocab"
D_MODEL = "d_model"      # residual stream
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
D_FF = "d_ff"            # MLP hidden
EXPERTS = "experts"      # MoE expert dim
D_EXPERT = "d_expert"    # MoE expert hidden
D_INNER = "d_inner"      # SSM inner dim
D_STATE = "d_state"      # SSM state dim
CONV_DIM = "conv_dim"
LORA = "lora"            # MLA latent dims
NONE = None              # unsharded (biases, norms, scalars)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int
    n_shared: int
    top_k: int
    d_expert: int              # per-expert FFN hidden dim
    n_dense_layers: int = 0    # leading layers that use a dense FFN instead
    router_aux_coef: float = 0.001
    capacity_factor: float = 1.25  # slack for EP fixed-capacity select


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora: int = 1536
    kv_lora: int = 512
    head_dim_nope: int = 128
    head_dim_rope: int = 64
    head_dim_v: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    norm_type: str = "rmsnorm"      # rmsnorm | layernorm
    act: str = "silu"               # silu (gated) | gelu
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid: every `attn_every`-th block is (shared) attention, rest SSM
    attn_every: int = 0
    shared_attn_block: bool = False
    # sliding-window attention (0 = full); used by dense archs for long_500k
    window: int = 0
    # enc-dec (audio): encoder depth/frames; frontend is a stub
    n_encoder_layers: int = 0
    encoder_frames: int = 1500
    # learned-position table size (enc-dec decoder); extended beyond the
    # real model's 448 to satisfy the assigned 32k prefill/decode shapes
    max_positions: int = 4096
    # vlm: number of stub image-patch embeddings prepended to the text
    n_patches: int = 0
    # MTP: extra next-next-token prediction head (deepseek-v3)
    mtp: bool = False
    # MLA decode: absorb wkv_b into q/out sides (no per-step KV expansion)
    mla_absorb: bool = False
    # route self-attention through the Pallas flash kernel (compiled on
    # TPU, interpreted on CPU — see repro.kernels.pallas_call); off, the
    # pure-jnp attention paths run
    use_flash_kernel: bool = False
    # FSDP unit size: layers per scan step (all-gather message granularity)
    scan_block_size: int = 1
    # activation-remat policy for scanned layer groups:
    # none | full | selective (dots_saveable)
    remat: str = "full"
    # source citation for the config
    source: str = ""

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class MeshContext:
    """Axis names the model needs when running distributed (None on 1 device)."""
    mesh: Any = None
    dp_axes: Tuple[str, ...] = ()      # batch axes, e.g. ("pod", "data")
    tp_axis: Optional[str] = None      # "model" (None => no TP / no EP)
    ep_enabled: bool = False           # route MoE through the shard_map EP path
    ep_axes: Tuple[str, ...] = ("model",)  # mesh axes experts shard over
    pp: int = 1                        # pipeline stage count (1 => unpipelined)
    pipe_axis: Optional[str] = None    # mesh axis the stage dim shards over
    n_micro: int = 0                   # microbatches (0 => 2*pp default)

    @property
    def dp_size(self) -> int:
        if self.mesh is None or not self.dp_axes:
            return 1
        import math

        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    @property
    def tp_size(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def ep_size(self) -> int:
        if self.mesh is None or not self.ep_axes:
            return 1
        import math

        return math.prod(self.mesh.shape[a] for a in self.ep_axes)


def constrain(x, mesh_ctx: Optional["MeshContext"], *rest):
    """Sharding-constrain an activation whose dim 0 is batch.

    ``rest`` entries are mesh-axis names (or None) for the remaining dims;
    entries are dropped when the dim isn't divisible. No-op without a mesh.
    Keeps sharding propagation honest inside scanned/checkpointed bodies.
    """
    if mesh_ctx is None or mesh_ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_ctx.mesh
    spec = [None] * x.ndim
    dp = mesh_ctx.dp_axes
    if dp and x.shape[0] % mesh_ctx.dp_size == 0:
        spec[0] = dp
    for i, ax in enumerate(rest[: x.ndim - 1], start=1):
        if ax is None:
            continue
        import math

        size = (math.prod(mesh.shape[a] for a in ax) if isinstance(ax, tuple)
                else mesh.shape[ax])
        if x.shape[i] % size == 0 and x.shape[i] >= size:
            spec[i] = ax
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec))
    )


class Model(abc.ABC):
    """The model IF (nn.Module analog)."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    @abc.abstractmethod
    def init(self, rng: jax.Array) -> Dict[str, Any]:
        ...

    @abc.abstractmethod
    def apply(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],
        mesh_ctx: Optional[MeshContext] = None,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Return (logits [B, S, vocab], aux-loss dict)."""

    @abc.abstractmethod
    def param_axes(self) -> Dict[str, Any]:
        """Pytree matching ``init`` output; leaves = tuple of logical axis names."""

    # -- serving -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> Any:
        raise NotImplementedError(f"{self.cfg.name}: no decode path")

    def decode_step(
        self,
        params: Dict[str, Any],
        cache: Any,
        tokens: jax.Array,          # [B] current tokens
        positions: jax.Array,       # [B] absolute positions
        mesh_ctx: Optional[MeshContext] = None,
        pages: Optional[jax.Array] = None,   # [B, n_pages] paged-cache tables
        active: Optional[jax.Array] = None,  # [B] write gate (paged only)
    ) -> Tuple[jax.Array, Any]:
        raise NotImplementedError(f"{self.cfg.name}: no decode path")

    def supports_paged_cache(self) -> bool:
        """Whether ``init_paged_cache``/``prefill_chunk`` and the paged
        ``decode_step`` are implemented for this architecture."""
        return False

    def init_paged_cache(self, n_blocks: int, block_len: int,
                         dtype=jnp.bfloat16) -> Any:
        """Block-pool decode cache: every leaf ``[L, n_blocks, block_len,
        ...]``; requests map blocks via per-slot page tables (see
        ``repro.serve.paging``) instead of owning a dense slot row."""
        raise NotImplementedError(f"{self.cfg.name}: no paged decode path")

    def insert_cache(self, cache: Any, request_cache: Any, slot) -> Any:
        """Write a batch=1 request cache into one slot of a slot-pool cache.

        ``cache`` is a pool from ``init_cache(n_slots, max_len)`` (every leaf
        is ``[L, n_slots, ...]`` — layer-stacked, slot axis 1); the request
        cache comes from ``prefill`` with batch 1 and the same ``max_len``.
        ``slot`` may be a traced scalar, so one compiled insert serves every
        slot.  The continuous-batching engine admits mid-flight requests with
        this (the whole slot row is overwritten — no stale state survives a
        slot's reuse).
        """
        def put(c, n):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), slot, axis=1)

        return jax.tree_util.tree_map(put, cache, request_cache)

    def abstract_params(self, rng=None) -> Dict[str, Any]:
        """Shape-only params via eval_shape (dry-run, no allocation)."""
        rng = jax.random.PRNGKey(0) if rng is None else rng
        return jax.eval_shape(self.init, rng)


def count_params(tree) -> int:
    import math

    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
