"""The comparison that decides ``correct``: the system's global models
after the first rounds against the plain reference's, from the same
seed, weights and batches.

Numbers compared (each against its limit in bench/limits/<cell>.json):

  d1_gap    change of the first round (the server's first update,
            g1 - g0): per leaf, |‖Δ_sys‖ - ‖Δ_ref‖| over the larger of
            ‖Δ_ref‖ and the median leaf's ‖Δ_ref‖; the worst leaf.
  d3_gap    the same for the change after three rounds (g3 - g0).
  loss_gap  each round's global model scored by the reference forward
            on a probe batch: the worst |L_sys - L_ref| / L_ref.

A leaf whose reference change is under a thousandth of the median
leaf's is left out of the gaps (it moves by round-off alone).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SKIP_BELOW = 1e-3


def leaves(tree) -> Dict[str, np.ndarray]:
    """Flatten a nested dict of arrays to ``{"a/b/c": array}``."""
    out = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(f"{prefix}/{k}" if prefix else k, node[k])
        else:
            out[prefix] = node
    walk("", tree)
    return out


def change_norms(after, before) -> Dict[str, float]:
    a, b = leaves(after), leaves(before)
    if a.keys() != b.keys():
        raise ValueError(f"leaf sets differ: {sorted(a.keys() ^ b.keys())}")
    return {k: float(np.linalg.norm(np.asarray(a[k], np.float64)
                                    - np.asarray(b[k], np.float64)))
            for k in a}


def norm_gap(sys_norms: Dict[str, float], ref_norms: Dict[str, float]):
    """Worst leaf's gap of change norms; returns ``(gap, leaf, skipped)``."""
    med = float(np.median(list(ref_norms.values())))
    skipped = [k for k, r in ref_norms.items() if r < SKIP_BELOW * med]
    worst, leaf = 0.0, None
    for k, r in ref_norms.items():
        if k in skipped:
            continue
        gap = abs(sys_norms[k] - r) / max(r, med)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap > worst or leaf is None:
            worst, leaf = gap, k
    return worst, leaf, skipped


def loss_gap(sys_losses: Sequence[float], ref_losses: Sequence[float]):
    gaps = [abs(s - r) / abs(r) if np.isfinite(s) else float("inf")
            for s, r in zip(sys_losses, ref_losses)]
    return max(gaps)


def compare(sys_models: List, ref_models: List, sys_losses, ref_losses
            ) -> Dict[str, dict]:
    """``sys_models``/``ref_models``: global models ``[g0, g1, g2, g3]``.
    Returns ``{number: {"value", "leaf"?}}``."""
    g0 = ref_models[0]
    out = {}
    for name, r in (("d1_gap", 1), ("d3_gap", 3)):
        gap, leaf, skipped = norm_gap(change_norms(sys_models[r], g0),
                                      change_norms(ref_models[r], g0))
        out[name] = {"value": gap, "leaf": leaf, "skipped": skipped}
    out["loss_gap"] = {"value": loss_gap(sys_losses, ref_losses)}
    return out


def verdict(numbers: Dict[str, dict], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(numbers[k]["value"])
               and numbers[k]["value"] <= limits[k] for k in limits)
