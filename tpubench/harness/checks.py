"""The comparison that decides ``correct``: the timed path's own output
against the plain reference, each number beside a limit of its own."""

from __future__ import annotations

import statistics

#: A leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding (a key's bias under softmax): Adam moves
#: it by round-off alone, so it is left out of the parameters' change.
DEAD_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, *, skip=()) -> tuple[float, str]:
    """The widest gap between the program's and the reference's norm of a
    leaf, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf)."""
    if set(prog) != set(ref):
        raise ValueError("program and reference name different leaves")
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, floor)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def dead_leaves(ref_grad_norms: dict) -> set:
    floor = DEAD_GRADIENT_SHARE * statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < floor}


def train_numbers(prog: dict, ref: dict) -> dict:
    """The numbers a training cell compares. ``prog`` and ``ref`` hold
    ``losses`` (the first steps, one each), ``grad_norms`` (first step)
    and ``delta_norms`` (after the last of those steps)."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"],
                                         ref["grad_norms"])
    dead = dead_leaves(ref["grad_norms"])
    delta_gap, delta_leaf = worst_leaf_gap(
        prog["delta_norms"], ref["delta_norms"], skip=dead)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap,
            "_where": {"grad_gap": grad_leaf, "delta_gap": delta_leaf,
                       "dead_leaves": len(dead)}}


def judge(numbers: dict, limits: dict) -> list[dict]:
    """One row for each number that has a limit: name, value, limit, ok.
    A number without a limit, or a limit without a number, is an error:
    nothing is compared by accident or skipped in silence."""
    rows = []
    names = {k for k in numbers if not k.startswith("_")}
    if names != set(limits):
        raise ValueError(f"numbers {sorted(names)} and limits "
                         f"{sorted(limits)} do not pair up")
    for name in sorted(names):
        value, limit = float(numbers[name]), float(limits[name])
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(value <= limit)})
    return rows
