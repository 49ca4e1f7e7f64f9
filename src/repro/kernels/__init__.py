"""Pallas TPU kernels for the compute hot spots (flash attention, SSD scan).

Every kernel is built through :func:`pallas_call`, the one place that
decides how a kernel runs. The choice follows the platform the program is
lowered for, not the default backend: a program compiled ahead of time for
a described TPU gets the compiled kernel even on a CPU host.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` compiled by Mosaic on TPU and run by the Pallas
    interpreter on CPU (the test suite); lowering for any other platform
    raises. ``interpret`` is not an argument: it is never the caller's
    choice."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    return lambda *args: jax.lax.platform_dependent(
        *args, tpu=compiled, cpu=interpreted)
