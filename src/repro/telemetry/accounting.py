"""MFU / goodput accounting.

This module is the single home of the model-FLOPs estimate the framework
uses everywhere: dryrun's roofline (``launch/dryrun.py`` delegates here),
the gym's bench report, and the per-run ``mfu`` result field.

Definitions (documented in docs/observability.md):

``model FLOPs/step``
    The classic 6·N_active·D training estimate (2·N_active·D per token
    for inference), with N_active discounting inactive routed experts
    for MoE configs — the same numerator dryrun's
    ``useful_flops_ratio`` uses.
``mfu``
    model FLOPs/step ÷ (measured step seconds × peak FLOP/s × devices),
    with the peak taken from :data:`PEAKS` by the ``device_kind`` the run
    measured on.  On the CPU no device metric exists, so ``mfu`` is not
    written; an accelerator missing from the table is an error, never a
    default.
``goodput``
    productive steps ÷ dispatched steps.  Rollback replays, anomaly
    skips, and steps discarded by preemption all dispatch work that
    never advances the optimizer, so they discount goodput; a clean run
    scores exactly 1.0.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect over four links.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "ici_link_bytes_s": 50e9},
}
#: The entry the dry-run roofline models (a described, not a measured chip).
V5E = PEAKS["TPU v5 lite"]


class UnknownDeviceError(ValueError):
    """An accelerator with no entry in :data:`PEAKS`."""


def device_peaks(device) -> Optional[Dict[str, float]]:
    """The :data:`PEAKS` entry of ``device``. ``None`` on the CPU, where no
    device metric is taken; an accelerator missing from the table raises
    :class:`UnknownDeviceError`."""
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no peak entry for {device.platform} device_kind "
            f"{device.device_kind!r}; known: {sorted(PEAKS)}") from None


def run_devices(mesh) -> list:
    """The devices a run computes on: its mesh's, or the default device."""
    import jax

    return list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]


def device_info(devices) -> Dict[str, Any]:
    """``platform``, ``device_kind`` and ``device_count`` of the devices a
    result was measured on."""
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def count_param_leaves(params) -> int:
    """Total element count over a pytree of arrays/ShapeDtypeStructs."""
    import jax

    return sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(params))


def active_params(cfg, n_total: int) -> int:
    """Discount inactive routed experts: only ``top_k`` of ``n_routed``
    expert MLPs run per token in a MoE layer."""
    if not getattr(cfg, "moe", None):
        return n_total
    per_layer_routed = 3 * cfg.d_model * cfg.moe.d_expert * cfg.moe.n_routed
    n_moe_layers = cfg.n_layers - cfg.moe.n_dense_layers
    active_frac = cfg.moe.top_k / cfg.moe.n_routed
    return n_total - int(per_layer_routed * n_moe_layers * (1 - active_frac))


def model_flops(cfg, shape) -> Tuple[float, int, int]:
    """6·N_active·D (training) or 2·N_active·D (per-token inference) for
    one global step of ``shape``.  Returns (flops, n_total, n_active).

    This is the function dryrun historically owned; it builds the model
    abstractly (``jax.eval_shape``) so no parameter memory is allocated.
    """
    import jax

    from ..models import build_model

    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_total = count_param_leaves(params)
    n_active = active_params(cfg, n_total)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens, n_total, n_active
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens, n_total, n_active
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_active * tokens, n_total, n_active


def flops_per_train_step(model, loader,
                         grad_accum: int = 1) -> Optional[float]:
    """Model FLOPs for one optimizer step of a live gym: 6·N_active ×
    (global_batch × seq_len).  Returns None when the loader does not
    expose its token geometry (custom loaders) or the model has no
    ArchConfig.  ``grad_accum`` microbatching does not change the token
    count per optimizer step, so it does not appear here.
    """
    import jax

    cfg = getattr(model, "cfg", None)
    gb = getattr(loader, "global_batch", None)
    seq = getattr(getattr(loader, "dataset", None), "seq_len", None)
    if cfg is None or not gb or not seq:
        return None
    try:
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    except Exception:
        return None
    n_total = count_param_leaves(params)
    n_active = active_params(cfg, n_total)
    return 6.0 * n_active * float(gb) * float(seq)


def mfu(flops_per_step: float, step_s: float, n_devices: int,
        peak_flops: float) -> float:
    """Model-FLOPs utilization of ``n_devices`` chips of ``peak_flops``."""
    if step_s <= 0 or n_devices <= 0 or peak_flops <= 0:
        return 0.0
    return flops_per_step / (step_s * peak_flops * n_devices)


def goodput(productive_steps: int, dispatched_steps: int) -> float:
    """Productive ÷ dispatched step ratio in [0, 1]; 1.0 when idle."""
    if dispatched_steps <= 0:
        return 1.0
    return max(0.0, min(1.0, productive_steps / dispatched_steps))


def tokens_per_s(global_batch: Any, seq_len: Any,
                 step_s: float) -> Optional[float]:
    if not global_batch or not seq_len or step_s <= 0:
        return None
    return float(global_batch) * float(seq_len) / step_s
