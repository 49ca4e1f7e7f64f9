"""Production mesh definitions (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} "
            "(dryrun.py sets XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    import numpy as np

    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_local_mesh(dp: int = 1, tp: int = 1, pp: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs).

    ``pp > 1`` prepends a ``pipe`` axis — the 3D ``(pipe, data, model)``
    mesh pipelined plans compose over; the 2-axis shape is unchanged
    otherwise so existing call sites keep their layouts.
    """
    import numpy as np

    n = dp * tp * pp
    devices = jax.devices()
    assert len(devices) >= n, f"need {n} devices, have {len(devices)}"
    if pp > 1:
        dev = np.asarray(devices[:n]).reshape((pp, dp, tp))
        return jax.sharding.Mesh(dev, ("pipe", "data", "model"))
    dev = np.asarray(devices[:n]).reshape((dp, tp))
    return jax.sharding.Mesh(dev, ("data", "model"))


def make_split_mesh(dp: int, tp: int):
    """Re-split a pod's chips into a dp x tp ("data", "model") mesh — the
    dry-run's mesh-split perf-tuning knob (e.g. 32x8 over the same 256)."""
    import numpy as np

    n = dp * tp
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices for a {dp}x{tp} split, "
                           f"have {len(devices)}")
    dev = np.asarray(devices[:n]).reshape(dp, tp)
    return jax.sharding.Mesh(dev, ("data", "model"))


# ---------------------------------------------------------------------------
# Mesh providers: the registry's mesh components. Construction is DATA (no
# device state is touched at resolve time); ``build()`` makes the mesh, once.
# ---------------------------------------------------------------------------
class MeshProvider:
    """Base provider: lazy, cached mesh construction."""

    _UNSET = object()

    def __init__(self) -> None:
        self._mesh = self._UNSET

    def build(self):
        if self._mesh is self._UNSET:
            self._mesh = self._make()
        return self._mesh

    def _make(self):  # pragma: no cover - overridden
        raise NotImplementedError


class SingleDeviceMesh(MeshProvider):
    """No mesh: the gym runs un-sharded on one device."""

    def _make(self):
        return None


class LocalMesh(MeshProvider):
    def __init__(self, dp: int = 1, tp: int = 1, pp: int = 1) -> None:
        super().__init__()
        self.dp, self.tp, self.pp = int(dp), int(tp), int(pp)

    def _make(self):
        return make_local_mesh(self.dp, self.tp, self.pp)


class ProductionMesh(MeshProvider):
    def __init__(self, multi_pod: bool = False) -> None:
        super().__init__()
        self.multi_pod = bool(multi_pod)

    def _make(self):
        return make_production_mesh(multi_pod=self.multi_pod)


class SplitMesh(MeshProvider):
    def __init__(self, dp: int, tp: int) -> None:
        super().__init__()
        self.dp, self.tp = int(dp), int(tp)

    def _make(self):
        return make_split_mesh(self.dp, self.tp)

