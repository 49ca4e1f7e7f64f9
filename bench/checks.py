"""The numbers that decide ``correct``: gaps between what the program
produced on its timed path and what the plain reference computes.

Training (first three optimizer steps of the window's own step and feed):

- ``loss_gap``: the largest |loss_program - loss_reference| over the steps;
- ``grad_norm_gap``: over every leaf (per layer), the gap between the
  program's and the reference's norm of the first gradient as the
  optimizer got it, over the larger of the reference leaf's norm and the
  median leaf's; the worst leaf;
- ``update_norm_gap``: the same for the change of the parameters over the
  three steps. Leaves whose reference gradient is under a thousandth of the
  median leaf's move under Adam by round-off alone and are left out.

Serving: ``logit_gap``, the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

from typing import Dict, Iterable, Set, Tuple

import numpy as np

ROUNDOFF_GRAD = 1e-3      # of the median leaf's gradient norm


def _median(norms: Dict[str, np.ndarray]) -> float:
    return float(np.median(np.concatenate([np.ravel(v)
                                           for v in norms.values()])))


def still_leaves(ref_grad: Dict[str, np.ndarray]) -> Set[Tuple[str, int]]:
    med = _median(ref_grad)
    return {(k, i) for k, v in ref_grad.items()
            for i, x in enumerate(np.ravel(v)) if x < ROUNDOFF_GRAD * med}


def norm_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             skip: Iterable[Tuple[str, int]] = ()) -> Tuple[float, str]:
    """Worst leaf's |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    skip = set(skip)
    med = _median({k: v for k, v in ref.items()})
    worst, where = 0.0, ""
    for k, rv in ref.items():
        pv = np.ravel(prog[k])
        for i, r in enumerate(np.ravel(rv)):
            if (k, i) in skip:
                continue
            g = abs(float(pv[i]) - float(r)) / max(float(r), med)
            if not np.isfinite(g) or g > worst:
                worst, where = (float("inf") if not np.isfinite(g) else g,
                                f"{k}[{i}]")
    return worst, where


def train_readings(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """``prog``/``ref``: dicts with ``losses``, ``grad_norms`` and
    ``update_norms`` (name -> per-layer norms)."""
    lg = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    loss = max(lg) if all(np.isfinite(lg)) else float("inf")
    return {
        "loss_gap": (loss, f"step {int(np.argmax(lg)) + 1}"),
        "grad_norm_gap": norm_gap(prog["grad_norms"], ref["grad_norms"]),
        "update_norm_gap": norm_gap(prog["update_norms"],
                                    ref["update_norms"],
                                    skip=still_leaves(ref["grad_norms"])),
    }


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: max(ref_logits) - ref_logits[token]."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - got
