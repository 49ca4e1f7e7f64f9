"""Weights from a seed: one jitted call on the device, float32.

The program and the references are both given these weights, made from the
same seed by the same function, so the reference never takes anything the
program made. The names and shapes come from the reference family's
``param_specs``; the program's adapter only rearranges the arrays.

A cell of several chips draws each leaf straight into its :func:`placement`
over those chips, so that no chip holds the whole float32 model. The
values are the one-device draw's, bit for bit (JAX's partitionable
threefry draws each element from its own index).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: str):
    """A PRNG key from any non-negative integer seed (wider than 32 bits is
    fine) and a stream label, so that weights, data and traffic of one seed
    are independent draws."""
    words = np.random.SeedSequence(
        [int(seed), zlib.crc32(stream.encode())]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _draw(key, shape, init):
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "around_one":
        return 1.0 + jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, init[1], init[2])
    if kind == "log_uniform":
        lo, hi = np.log(init[1]), np.log(init[2])
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if kind == "softplus_inverse_log_uniform":
        lo, hi = np.log(init[1]), np.log(init[2])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    raise ValueError(f"unknown init {init!r}")


def placement(specs: dict, devices) -> dict:
    """name -> sharding over ``devices``: each leaf split along its first
    axis that the number of devices divides, replicated where none does.
    A stacked ``layers/`` leaf is split after its layer axis where it can
    be, so that a scan over the layers slices it without gathering it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("chips",))
    n = len(devices)
    out = {}
    for name, (shape, _) in specs.items():
        order = list(range(len(shape)))
        if name.startswith("layers/"):
            order = order[1:] + order[:1]
        axis = next((i for i in order if shape[i] % n == 0), None)
        spec = () if axis is None else (None,) * axis + ("chips",)
        out[name] = NamedSharding(mesh, P(*spec))
    return out


def make(specs: dict, seed: int, devices=None) -> dict:
    """name -> float32 array, for ``specs`` of ``name -> (shape, init)``: on
    the default device, or with ``devices`` (more than one) in their
    :func:`placement`. A ``log_uniform`` init is drawn as the log of the
    value (the parameter is stored as a logarithm)."""
    names = sorted(specs)

    def gen(key):
        return {n: _draw(jax.random.fold_in(key, i), tuple(specs[n][0]),
                         specs[n][1]) for i, n in enumerate(names)}

    if devices is None:
        return jax.jit(gen)(seed_key(seed, "weights"))
    return jax.jit(gen, out_shardings=placement(specs, devices))(
        seed_key(seed, "weights"))
