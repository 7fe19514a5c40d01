"""The comparison that decides ``correct`` for a training cell.

Both sides (the program's timed path and the plain reference) report, for
the first three rounds from the same inputs: each round's loss, and the L2
norm of every leaf's change after round 1 and after round 3, over the
global parameters and the stacked local heads. The numbers compared:

* ``loss``: the largest relative gap of a round's loss, ``|p - r| / |r|``;
* ``grad1``: round 1's change — with SGD the first update the optimizer
  applied, ``-lr`` times the round's pseudo-gradient — by the worst leaf:
  ``|norm_p - norm_r| / max(norm_r, median leaf norm_r)``;
* ``change3``: the same for the change after three rounds (the state the
  window's first round starts from).

Leaves whose round-1 change in the reference is below a thousandth of the
median leaf's move by round-off alone (the global copy of the local head,
which no round trains, reads exactly 0) and are left out of both.

A number that is not finite is a failure. Each number has a limit in the
cell's file (``bench/workloads/<cell>.json``).
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss", "grad1", "change3")
MOVED_FLOOR = 1e-3


def _leaf_gap(prog: dict, ref: dict, keep):
    med = float(np.median([ref[k] for k in keep]))
    worst, leaf = 0.0, None
    for k in keep:
        p = prog.get(k, float("nan"))
        gap = abs(p - ref[k]) / max(ref[k], med)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap >= worst:
            worst, leaf = gap, k
    return worst, leaf


def readings(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [3 floats], "d1": {leaf: norm}, "d3": {...}}.
    Returns {name: value} plus ``leaves`` naming the worst leaf of each."""
    losses = []
    for p, r in zip(prog["losses"], ref["losses"]):
        losses.append(abs(p - r) / abs(r) if math.isfinite(p)
                      else float("inf"))
    med = float(np.median(list(ref["d1"].values())))
    keep = sorted(k for k, v in ref["d1"].items() if v >= MOVED_FLOOR * med)
    g1, leaf1 = _leaf_gap(prog["d1"], ref["d1"], keep)
    g3, leaf3 = _leaf_gap(prog["d3"], ref["d3"], keep)
    return {"loss": max(losses) if losses else float("inf"),
            "grad1": g1, "change3": g3,
            "leaves": {"grad1": leaf1, "change3": leaf3},
            "left_out": sorted(set(ref["d1"]) - set(keep))}


def judge(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) — every number at or under its
    limit, and finite."""
    checks = {n: {"value": float(values[n]), "limit": float(limits[n])}
              for n in NAMES}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
