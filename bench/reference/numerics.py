"""Matrix products and norms shared by the plain references.

Every matrix product of a reference goes through :func:`mm`, which runs it
in one of three precisions:

- ``"f32"``: float32 operands at ``Precision.HIGHEST`` (on a TPU a float32
  product otherwise runs in bfloat16 passes);
- ``"bf16"``: bfloat16 operands, float32 accumulation;
- ``"fp8"``: operands fake-quantized to float8 e4m3 with one scale per
  tensor, and (under autodiff) the incoming cotangent quantized the same
  way before the backward products. This is the control: the step below
  the bfloat16 compute a configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")
_E4M3_MAX = 448.0


def _fake_fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@jax.custom_vjp
def _quant_operand(x):
    return _fake_fp8(x)


def _quant_operand_fwd(x):
    return _fake_fp8(x), None


def _quant_operand_bwd(_, g):
    return (g,)             # straight-through: the product's backward sees g


_quant_operand.defvjp(_quant_operand_fwd, _quant_operand_bwd)


@jax.custom_vjp
def _quant_cotangent(y):
    return y


def _quant_cotangent_fwd(y):
    return y, None


def _quant_cotangent_bwd(_, g):
    return (_fake_fp8(g),)


_quant_cotangent.defvjp(_quant_cotangent_fwd, _quant_cotangent_bwd)


def mm(spec: str, a, b, mode: str = "f32"):
    """``jnp.einsum(spec, a, b)`` in the precision ``mode``; float32 out."""
    if mode == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "fp8":
        out = jnp.einsum(spec, _quant_operand(a), _quant_operand(b),
                         precision=jax.lax.Precision.HIGHEST)
        return _quant_cotangent(out)
    raise ValueError(f"unknown precision mode {mode!r}; one of {MODES}")


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def cross_entropy_sum(logits, labels):
    """Summed token NLL of float32 ``logits [..., V]`` at ``labels``."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)
