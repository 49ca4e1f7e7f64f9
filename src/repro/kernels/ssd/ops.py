"""Jit'd public wrapper for the SSD chunk-scan kernel.

Differentiable: forward runs the Pallas kernel; backward recomputes through
the chunked jnp oracle (recompute vjp, no kernel residuals)."""
from __future__ import annotations

import functools

import jax


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_vjp(x, dt, A, Bm, Cm, D_skip, chunk):
    from .kernel import ssd_scan

    return ssd_scan(x, dt, A, Bm, Cm, D_skip, chunk=chunk)


def ssd(x, dt, A, Bm, Cm, D_skip, *, chunk: int = 128):
    return _ssd_vjp(x, dt, A, Bm, Cm, D_skip, chunk)


def _fwd(x, dt, A, Bm, Cm, D_skip, chunk):
    out = _ssd_vjp(x, dt, A, Bm, Cm, D_skip, chunk)
    return out, (x, dt, A, Bm, Cm, D_skip)


def _bwd(chunk, res, g):
    from ...models.ssm import ssd_chunked

    x, dt, A, Bm, Cm, D_skip = res
    _, vjp = jax.vjp(
        lambda x, dt, A, Bm, Cm, D: ssd_chunked(x, dt, A, Bm, Cm, D,
                                                chunk=chunk)[0],
        x, dt, A, Bm, Cm, D_skip,
    )
    return vjp(g)


_ssd_vjp.defvjp(_fwd, _bwd)
