"""The comparison that decides ``correct``: the program's first steps
against the plain reference (bench/reference.py).

A step is one call of the window. Four numbers are compared, each against
its own limit (``bench/limits/<cell>.json``):

- ``first_loss_gap``: the largest relative gap of the first round's loss,
  over the lanes (both sides start from the same weights and batches);
- ``loss_gap``: the largest relative gap of a round's loss, over every lane
  and round of the steps;
- ``step1_change_gap`` and ``last_change_gap``: per lane and parameter leaf,
  the gap between the norms of the change the program made and the change
  the reference made, after step 1 and after the last step, as a share of
  the reference's norm of that leaf or of the median leaf, whichever is
  larger; the worst leaf counts;
- ``step1_change_diff`` and ``last_change_diff``: the same, with the norm
  of the difference of the two changes in place of the gap of their norms.
  A change of the right size in the wrong direction (a client trained on
  part of its batch) shows here and not in the gap of norms.

Leaves whose step-1 change in the reference is under a thousandth of the
median leaf's (a gradient that is zero to rounding) are left out of the
change numbers.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("first_loss_gap", "loss_gap", "step1_change_gap",
           "last_change_gap", "step1_change_diff", "last_change_diff")
TINY = 1e-3


def _norms(a: dict, b: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(a[k], np.float64)
                                    - np.asarray(b[k], np.float64)))
            for k in b}


def _diff_norms(p: list, r: list, step: int) -> dict:
    """Per leaf, the norm of (program's change) - (reference's change)."""
    return {k: float(np.linalg.norm(
        (np.asarray(p[step][k], np.float64) - np.asarray(p[0][k], np.float64))
        - (np.asarray(r[step][k], np.float64)
           - np.asarray(r[0][k], np.float64)))) for k in r[0]}


def change_gap(prog: list, ref: list, step: int, diff: bool = False) -> float:
    """Worst-leaf gap of the change norms after ``step`` (index into the
    kept parameters), over lanes; with ``diff`` the norm of the changes'
    difference instead."""
    worst = 0.0
    for p_lane, r_lane in zip(prog, ref):
        first = _norms(r_lane[1], r_lane[0])
        med1 = float(np.median(list(first.values())))
        ref_n = _norms(r_lane[step], r_lane[0])
        if diff:
            apart = _diff_norms(p_lane, r_lane, step)
        else:
            prog_n = _norms(p_lane[step], p_lane[0])
            apart = {k: abs(prog_n[k] - r) for k, r in ref_n.items()}
        med = float(np.median(list(ref_n.values())))
        for k, r in ref_n.items():
            if first[k] < TINY * med1:
                continue
            gap = apart[k] / max(r, med, 1e-30)
            worst = max(worst, gap if np.isfinite(gap) else np.inf)
    return worst


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers; ``prog`` and ``ref`` as ``reference.run``
    returns them (losses (lanes, rounds), params per lane, each a flat
    dict of leaves by path as ``reference.flat`` makes it, so a nested
    parameter tree is compared leaf by leaf)."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"loss shapes differ: {lp.shape} vs {lr.shape}")
    rel = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-12)
    rel[~np.isfinite(rel)] = np.inf
    return {"first_loss_gap": float(np.max(rel[:, 0])),
            "loss_gap": float(np.max(rel)),
            "step1_change_gap": change_gap(prog["params"], ref["params"], 1),
            "last_change_gap": change_gap(prog["params"], ref["params"], -1),
            "step1_change_diff": change_gap(prog["params"], ref["params"], 1,
                                            diff=True),
            "last_change_diff": change_gap(prog["params"], ref["params"], -1,
                                           diff=True)}


def judge(values: dict, limits: dict | None) -> list:
    """[{"name", "value", "limit", "ok"}] per compared number; without
    limits nothing passes. A number its limits file marks
    ``"compared": false`` (it has no reading to separate from) is left
    out."""
    out = []
    for name in NUMBERS:
        entry = (limits or {}).get(name, {})
        if entry.get("compared") is False:
            continue
        lim = entry.get("limit")
        v = values[name]
        ok = lim is not None and np.isfinite(v) and v <= lim
        out.append({"name": name, "value": v, "limit": lim, "ok": bool(ok)})
    return out
