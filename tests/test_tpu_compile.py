"""The Pallas kernels compile for a TPU v5e at the widths of the models that
use them. The chip is described, not attached: nothing runs, so these tests
catch what the TPU compiler refuses (tile shapes, memory spaces, VMEM) and
that the kernel really is in the program, at no chip time."""
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
