"""The arithmetic of ``correct``: gaps between what the program produced
and what the plain reference produced, and their limits."""

from __future__ import annotations

import math

import numpy as np


def leaf_gaps(got: dict, want: dict) -> dict:
    """Per leaf, the gap between the two norms (not the norm of the
    difference) over the reference's norm of that leaf or of the median
    leaf, whichever is larger: some gradients are all but zero."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    norm = {k: float(np.linalg.norm(np.asarray(want[k], np.float64)))
            for k in want}
    floor = float(np.median(list(norm.values())))
    return {
        k: abs(float(np.linalg.norm(np.asarray(got[k], np.float64))) - norm[k])
        / max(norm[k], floor, 1e-300)
        for k in want}


def worst_leaf_gap(got: dict, want: dict) -> float:
    return max(leaf_gaps(got, want).values())


def median_leaf_gap(got: dict, want: dict) -> float:
    return float(np.median(list(leaf_gaps(got, want).values())))


def judge(numbers: dict, limits: dict):
    """(correct, table): a cell's limits file names the numbers compared,
    each with a limit of its own; what a driver reads beside them is left
    out of the table. A number that is missing or not finite fails."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        table[name] = {"value": value, "limit": limit}
    return ok and bool(limits), table
