"""The comparison that decides ``correct``.

Four numbers, each with a limit from the cell's traffic file:

* ``grad1_gap``: the first round's step as the program took it,
  theta_0 - theta_1, against the reference's, by the worst leaf: the gap
  between the two leaf norms over the reference's norm of that leaf or
  of the median leaf, whichever is larger;
* ``change3_gap``: the same for the change after three rounds,
  theta_3 - theta_0, over the leaves whose reference step is at least
  a thousandth of the median leaf's (a leaf the loss does not reach
  moves by rounding alone);
* ``c1c2_gap``: the widest gap of a client's C1*C2 statistic (Eq. 2-5)
  in rounds 1 and 3;
* ``keep_mismatch``: the clients whose keep decision differs, in rounds
  1 and 3 (an exact comparison: limit 0).
"""
from __future__ import annotations

import numpy as np

NAMES = ("grad1_gap", "change3_gap", "c1c2_gap", "keep_mismatch")
MOVED_FLOOR = 1e-3


def leaf_gap(prog: np.ndarray, ref: np.ndarray, counted=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if counted is not None:
        prog, ref = prog[counted], ref[counted]
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / np.maximum(floor, 1e-30)))


def keep_from_c1c2(c1c2, eps) -> np.ndarray:
    """The keep mask a round's C1*C2 log implies: with eps1 = 0, C1*C2
    is C2 when the update points the guide's way and <= 0 otherwise."""
    eps1, eps2, eps3 = eps
    if eps1 != 0:
        raise ValueError("the keep mask is read back for eps1 = 0 only")
    c = np.asarray(c1c2)
    return (c > eps2) & (c < eps3)


def numbers(prog: dict, ref: dict) -> dict:
    """The four compared numbers, from two sets of readings (each with
    ``grad1``, ``change3``, ``c1c2`` (2, N) and ``keep`` (2, N))."""
    g = np.asarray(ref["grad1"], np.float64)
    counted = g >= MOVED_FLOOR * np.median(g)
    return {
        "grad1_gap": leaf_gap(prog["grad1"], ref["grad1"]),
        "change3_gap": leaf_gap(prog["change3"], ref["change3"], counted),
        "c1c2_gap": float(np.max(np.abs(np.asarray(prog["c1c2"], np.float64)
                                        - np.asarray(ref["c1c2"])))),
        "keep_mismatch": float(np.sum(np.asarray(prog["keep"])
                                      != np.asarray(ref["keep"]))),
    }


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers whose limit
    is not None; a number that is not finite fails.  A cell leaves a
    number uncompared (limit None) where neither the control nor a
    fault separates it from sound runs."""
    out, ok = {}, True
    for name in NAMES:
        v, lim = nums[name], limits[name]
        if lim is None:
            continue
        out[name] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, out


def threshold_margin(c1c2, eps) -> float:
    """How near any client's C1*C2 came to a keep threshold, relative to
    it: a sound run whose statistic sits within rounding of eps2 or
    eps3 may decide the other way."""
    c = np.asarray(c1c2, np.float64)
    _, e2, e3 = eps
    return float(min(np.min(np.abs(c - e2)) / e2, np.min(np.abs(c - e3)) / e3))
