"""The comparison that decides ``correct`` for a training cell: the timed
call's own first steps against the plain reference.

Each number is a gap of norms by the worst leaf: the distance between the
program's norm and the reference's (not the norm of their difference), over
the reference's norm of that leaf or of the median leaf, whichever is larger,
since some gradients are all but zero.
"""

import statistics

TINY_GRAD = 1e-3  # of the median leaf's: such a leaf moves by round-off alone


def worst_leaf_gap(prog, ref, skip=()):
    """-> (gap, leaf). A leaf on one side only is a structural fault: inf."""
    if set(prog) != set(ref):
        return float("inf"), "leaves differ: " + ",".join(sorted(set(prog) ^ set(ref))[:4])
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        floor = max(r, med)
        gap = abs(prog[leaf] - r) / floor if floor > 0 else (0.0 if prog[leaf] == r else float("inf"))
        if not gap <= worst:  # NaN counts as worst
            worst, where = gap, leaf
    return worst, where


def flat_grad_leaves(ref_grad_norms):
    """Leaves whose gradient is nought to rounding in the reference (a rule on
    the reference's gradient, never a name): left out of the change."""
    med = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < TINY_GRAD * med}


def numbers(prog, ref):
    """``prog`` and ``ref``: {"losses": [...], "grad_norms": {leaf: norm},
    "change_norms": {leaf: norm}}. -> {name: (value, where)}."""
    worst, where = 0.0, "no step"
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        gap = abs(p - r) / abs(r)
        if not gap <= worst:  # NaN counts as worst
            worst, where = gap, f"step {i}"
    if len(prog["losses"]) != len(ref["losses"]):
        worst, where = float("inf"), "steps differ"
    out = {"loss_gap": (worst, where)}
    out["grad_gap"] = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    out["change_gap"] = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"], skip=flat_grad_leaves(ref["grad_norms"]))
    return out
