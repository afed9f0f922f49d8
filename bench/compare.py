"""The numbers that decide ``correct``: the program's first three training
steps against the plain reference's, from the same seed, weights and rows.

- ``loss``: the worst of the three steps' relative loss gaps.
- ``grad``: the first step's clipped gradient, as the optimizer holds it
  after one step; by the worst leaf, the gap between the two norms of that
  leaf over the larger of the reference's norm of that leaf and of the
  median leaf.
- ``update``: the same of each leaf's change over the three steps, leaving
  out leaves whose reference gradient is under a thousandth of the median
  leaf's (Adam moves those by round-off alone).
"""
from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3  # a leaf's gradient below this share of the median leaf's


def _worst_leaf_gap(prog: dict, ref: dict, leaves) -> float:
    leaves = list(leaves)
    med = float(np.median([ref[p] for p in leaves]))
    gaps = [abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30) for p in leaves]
    return max(gaps)


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"loss": [3 floats], "grad": {leaf: norm},
    "update": {leaf: norm}} -> {name: reading}."""
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(prog["loss"], ref["loss"]))
    grad = _worst_leaf_gap(prog["grad"], ref["grad"], ref["grad"])
    med = float(np.median(list(ref["grad"].values())))
    moved = [p for p, n in ref["grad"].items() if n >= NOUGHT * med]
    update = _worst_leaf_gap(prog["update"], ref["update"], moved)
    return {"loss": loss, "grad": grad, "update": update}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a missing or non-finite
    reading is not correct."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = values.get(name, float("nan"))
        checks[name] = {"value": v, "limit": limit}
        if not (math.isfinite(v) and v <= limit):
            ok = False
    return ok, checks
