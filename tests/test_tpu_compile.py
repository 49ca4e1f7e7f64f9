"""The Pallas kernels, and the serve engine's paged programs, compile for a
TPU v5e at the widths of the models that use them. The chip is described,
not attached: nothing runs, so these tests catch what the TPU compiler
refuses (tile shapes, memory spaces, VMEM), that the kernel really is in the
program, and what the compiler adds around it (copies), at no chip time."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash.ops import flash_attention
from repro.kernels.ssd.ops import ssd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_forward_compiles_at_qwen1p5_0p5b_widths(one_chip):
    cfg = get_config("qwen1p5_0p5b")
    B, S, H, K, dh = 4, 2048, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = _spec((B, S, H, dh), jnp.bfloat16, one_chip)
    kv = _spec((B, S, K, dh), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_780m_widths(one_chip):
    cfg = get_config("mamba2_780m")
    ssm = cfg.ssm
    H = ssm.expand * cfg.d_model // ssm.head_dim
    B, S, P, G, N = 2, 2048, ssm.head_dim, ssm.n_groups, ssm.d_state
    assert (H, P, N, ssm.chunk) == (48, 64, 128, 128)
    compiled = jax.jit(lambda *a: ssd(*a, chunk=ssm.chunk)).lower(
        _spec((B, S, H, P), jnp.bfloat16, one_chip),
        _spec((B, S, H), jnp.float32, one_chip),
        _spec((H,), jnp.float32, one_chip),
        _spec((B, S, G, N), jnp.bfloat16, one_chip),
        _spec((B, S, G, N), jnp.bfloat16, one_chip),
        _spec((H,), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_programs_keep_the_pool_in_place_at_qwen1p5_0p5b_widths(
        one_chip):
    """The serve engine's decode tick and prefill chunk, at the model's
    attention widths (two layers, a small vocabulary), update the donated
    bfloat16 page pool in place on a v5e: no copy of a pool leaf, into a
    layout of the compiler's choice or out of the layer loop, appears in
    either program."""
    import re

    from repro.models import build_model
    from repro.train import steps as ST

    model = build_model(get_config("qwen1p5_0p5b").with_(n_layers=2,
                                                         vocab=1024))
    n_slots, max_pages, n_blocks, bl = 4, 8, 256, 16

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(n_blocks, bl, jnp.bfloat16)))
    i32 = _spec((), jnp.int32, one_chip)
    slots = {k: _spec((n_slots,), dt, one_chip) for k, dt in (
        ("tokens", jnp.int32), ("pos", jnp.int32), ("active", bool),
        ("n_gen", jnp.int32), ("max_gen", jnp.int32), ("eos", jnp.int32),
        ("temperature", jnp.float32), ("top_k", jnp.int32),
        ("top_p", jnp.float32))}
    slots["key"] = _spec((n_slots, 2), jnp.uint32, one_chip)
    tick = jax.jit(ST.make_engine_step(model, paged=True),
                   donate_argnums=(1, 2)).lower(
        params, pool, slots,
        _spec((n_slots, max_pages), jnp.int32, one_chip)).compile()
    chunk = jax.jit(ST.make_prefill_chunk_step(model),
                    donate_argnums=(1,)).lower(
        params, pool, _spec((max_pages,), jnp.int32, one_chip),
        _spec((2 * bl,), jnp.int32, one_chip), i32, i32).compile()
    shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(pool)}
    copied = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")
    for compiled in (tick, chunk):
        copies = {tuple(int(d) for d in m.group(1).split(","))
                  for m in copied.finditer(compiled.as_text()) if m.group(1)}
        assert not copies & shapes
