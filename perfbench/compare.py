"""The comparisons that decide `correct`. Each returns {name: reading}; the
limits stand in the cell's file and `judge` sets them side by side."""

from __future__ import annotations

import statistics

STILL = 1e-3  # of the median leaf's gradient: below it a leaf moves by round-off


def _leaf_gaps(prog: dict, ref: dict, skip=()) -> tuple[float, str, float]:
    """Gap between the program's and the reference's norm of each leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns (worst gap, its leaf, the median gap)."""
    med = statistics.median(ref.values())
    gaps = {path: abs(prog[path] - r) / max(r, med, 1e-30)
            for path, r in ref.items() if path not in skip}
    where = max(gaps, key=gaps.get)
    return gaps[where], where, statistics.median(gaps.values())


def _leaf_diff(prog: dict, ref: dict) -> float | None:
    """Norm of (program's first gradient less the reference's) over the
    reference's norm, worst of the leaves handed over whole. Where the gaps
    of norms above cannot tell a noisier gradient from a sound one (PERF.md
    section 2: a ReLU net's gradient at initialisation keeps its norm when
    its direction is lost), this can."""
    import numpy as np

    diffs = [float(np.linalg.norm(np.asarray(prog[p], np.float64) - np.asarray(r, np.float64))
                   / max(float(np.linalg.norm(np.asarray(r, np.float64))), 1e-30))
             for p, r in ref.items() if p in prog]
    return max(diffs) if diffs else None


def train(prog: dict, ref: dict) -> tuple[dict, dict]:
    """prog, ref: {"loss": [..], "grad_norm": {leaf: n}, "delta_norm": {leaf: n},
    "grad_leaf": {leaf: array}}. Returns (readings, where the worst leaves are)."""
    loss_gaps = [abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])]
    med = statistics.median(ref["grad_norm"].values())
    still = {p for p, g in ref["grad_norm"].items() if g < STILL * med}
    g_gap, g_leaf, g_med = _leaf_gaps(prog["grad_norm"], ref["grad_norm"])
    d_gap, d_leaf, d_med = _leaf_gaps(prog["delta_norm"], ref["delta_norm"], still)
    readings = {"loss_gap": max(loss_gaps), "loss_gap_first": loss_gaps[0],
                "grad_norm_gap": g_gap, "grad_norm_gap_median": g_med,
                "delta_norm_gap": d_gap, "delta_norm_gap_median": d_med}
    diff = _leaf_diff(prog.get("grad_leaf", {}), ref.get("grad_leaf", {}))
    if diff is not None:
        readings["grad_diff"] = diff
    return (readings,
            {"grad_norm_gap": g_leaf, "delta_norm_gap": d_leaf,
             "still_leaves": sorted(still)})


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Every reading that has a limit, beside it. A reading with no limit in
    the cell's file is printed and not compared (PERF.md says which and
    why); a limit with no reading fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and value == value and value <= limit
        compared[name] = {"value": value, "limit": limit}
        ok = ok and good
    for name, value in readings.items():
        if name not in limits:
            compared[name] = {"value": value, "limit": None}
    return ok, compared
