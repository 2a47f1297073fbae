"""The comparison that decides ``correct`` for a training cell.

Four numbers, each the worst case over what it covers:

* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over the compared steps;
* ``grad_gap``: over the leaves, ``| |g_prog| - |g_ref| |`` of the first
  gradient as the optimizer applies it (clipped), over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``grad_diff``: over the moving leaves (below), ``|g_prog - g_ref|`` over
  ``|g_ref|`` of the same first gradient as vectors, at the positions
  ``layout.sample_positions`` draws from the seed. Both sides take it at
  the seed's weights, so it reads the rounding of one gradient and nothing
  of the steps after;
* ``change_gap``: like ``grad_gap``, of the parameters' change after the
  compared steps.

A leaf is moving when its reference gradient is at least a thousandth of
the median leaf's; a leaf below that moves under Adam by round-off alone.
Which numbers a cell compares, and their limits, are in
``limits/<workload>.json``; a number passes when it is at most its limit.
The others are readings, printed but not compared.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "grad_diff", "change_gap")
QUIET_LEAF = 1e-3


def _worst(prog: Dict[str, float], ref: Dict[str, float], keys
           ) -> Tuple[float, str]:
    """The largest gap over ``keys``; a leaf whose norm is not finite on
    either side reads ``inf``."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k in keys:
        gap = abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(gap):
            return math.inf, k
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def _worst_diff(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                keys) -> Tuple[float, str]:
    """The largest relative distance of two leaves' sampled elements; a
    leaf missing or not finite on the program's side reads ``inf``."""
    worst, where = 0.0, ""
    for k in keys:
        if k not in prog or prog[k].shape != ref[k].shape:
            return math.inf, k
        r = float(np.linalg.norm(ref[k]))
        gap = float(np.linalg.norm(prog[k] - ref[k])) / max(r, 1e-30)
        if not math.isfinite(gap):
            return math.inf, k
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def gaps(prog: dict, ref: dict) -> Tuple[Dict[str, float], Dict[str, str]]:
    """The numbers, and the leaf that sets each per-leaf one."""
    if len(prog["losses"]) != len(ref["losses"]):
        loss = float("inf")
    else:
        loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                       ref["losses"]))
    grad, g_leaf = _worst(prog["grad"], ref["grad"], ref["grad"])
    med = statistics.median(ref["grad"].values())
    moving = [k for k, v in ref["grad"].items() if v >= QUIET_LEAF * med]
    diff, d_leaf = _worst_diff(prog["grad_sample"], ref["grad_sample"],
                               moving)
    change, c_leaf = _worst(prog["change"], ref["change"], moving)
    return ({"loss_gap": loss, "grad_gap": grad, "grad_diff": diff,
             "change_gap": change},
            {"grad_gap": g_leaf, "grad_diff": d_leaf, "change_gap": c_leaf})


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """Each number the limits name, beside its limit; no limit fails. A
    value that is not a finite number fails and is written as a string, so
    that the result stays plain JSON."""
    checks = {name: {"value": numbers.get(name), "limit": limit}
              for name, limit in limits.items()}
    ok = bool(checks)
    for c in checks.values():
        v = c["value"]
        if v is None or not math.isfinite(v):
            ok = False
            c["value"] = str(v)
        elif v > c["limit"]:
            ok = False
    return ok, checks
