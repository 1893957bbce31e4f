"""The comparison that decides ``correct``: the program's first meta steps
against the plain reference's, from the same seed.

The numbers (a cell's workload file, ``bench/workloads/<cell>.json``,
gives a limit to those it compares, with the readings it was set from):

loss_gap               the largest |program loss - reference loss| over
                       the compared meta steps
first_grad_gap         over the leaves, the largest gap between the
                       program's and the reference's norm of the block
                       momentum after the first meta step (the first
                       gradient the meta optimiser gets), over the larger
                       of the reference's norm of that leaf and of the
                       median leaf
first_move_gap         the same for the learners' mean movement in the
                       first meta step, mean_j w_j - bfloat16(w~): the
                       block momentum's first gradient without the
                       rounding of w~ into the learners' copies, which
                       both sides compute alike
change_gap             the same for the change of the meta parameters
                       over the compared meta steps
*_median               the median over the leaves of the same gaps
first_move_gap.<leaf>  the first_move gap of one leaf

Leaves whose first local gradient in the reference is under a thousandth
of the median leaf's are nought to rounding and left out of the gaps.
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "first_grad_gap", "first_move_gap", "change_gap",
           "first_grad_gap_median", "first_move_gap_median",
           "change_gap_median")


def kept_leaves(local_grad: dict[str, float]) -> list[str]:
    med = statistics.median(local_grad.values())
    return [k for k, g in local_grad.items() if g >= 1e-3 * med]


def leaf_gaps(prog: dict, ref: dict, leaves) -> list[float]:
    med = statistics.median(ref[k] for k in leaves)
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves]
    return [g if math.isfinite(g) else math.inf for g in gaps]


def compare(prog: dict, ref: dict) -> dict[str, float]:
    """The numbers, from two sets of readings of the same steps."""
    if set(prog["first_grad"]) != set(ref["first_grad"]):
        raise ValueError("the program's leaves are not the reference's: "
                         f"{sorted(set(prog['first_grad']) ^ set(ref['first_grad']))}")
    n = len(ref["loss"])
    gaps = [abs(a - b) for a, b in zip(prog["loss"][:n], ref["loss"])]
    leaves = kept_leaves(ref["local_grad"])
    first = leaf_gaps(prog["first_grad"], ref["first_grad"], leaves)
    move = leaf_gaps(prog["first_move"], ref["first_move"], leaves)
    change = leaf_gaps(prog["change"], ref["change"], leaves)
    numbers = {
        "loss_gap": max(gaps) if all(map(math.isfinite, gaps)) else math.inf,
        "first_grad_gap": max(first),
        "first_move_gap": max(move),
        "change_gap": max(change),
        "first_grad_gap_median": _median(first),
        "first_move_gap_median": _median(move),
        "change_gap_median": _median(change),
    }
    numbers.update({f"first_move_gap.{k}": g for k, g in zip(leaves, move)})
    return numbers


def _median(gaps: list[float]) -> float:
    return statistics.median(gaps) if all(map(math.isfinite, gaps)) \
        else math.inf


def judge(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """True when every number that has a limit lies within it."""
    return all(numbers[k] <= lim for k, lim in limits.items())


def report(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """Each number compared beside its limit, for the result line."""
    return {k: {"value": numbers[k], "limit": lim}
            for k, lim in limits.items()}
