"""The comparison that decides ``correct`` in a training cell.

Both sides hand in the same readings of the first steps the timed object
took: the loss of every step, the norm of every leaf's first gradient as
the optimizer got it, the norm of every trained leaf's change after the
last of those steps, and the norm of the change of every running
statistic.  ``compare`` reduces them to ``NUMBERS``, each with a limit of
its own from ``limits/<workload>.json``; a run is correct when every
number is at or under its limit.

The norms are compared by the worst leaf: the gap between the program's
norm and the reference's (not the norm of their difference), measured
against the reference's norm of that leaf or of the median leaf, whichever
is larger, because some gradients are all but zero.
"""
from __future__ import annotations

import json
import math
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
# a leaf whose first gradient is under this share of the median leaf's is
# nought to rounding in the reference (a bias in front of a BatchNorm, whose
# true gradient is zero): what the program holds for it is the round-off of
# a sum of some hundred thousand bfloat16 terms, and it moves by that alone.
# Such leaves are left out of the gradient's and the update's comparison
NOUGHT_GRADIENT = 1e-3


def load_limits(workload):
    """The limits of ``workload`` from ``limits/<workload>.json``: number
    -> limit.  A cell without the file cannot be judged, and fails.  A
    number the file leaves out is not compared in that cell; the file says
    why under ``not_compared``."""
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


NUMBERS = ("loss_gap", "grad_norm_gap", "grad_norm_gap_median",
           "update_norm_gap", "update_norm_gap_median", "stats_norm_gap")


def _leaf_gaps(program, reference, leaves):
    """(worst gap, its leaf, median gap) of the norms over ``leaves``."""
    floor = statistics.median(reference[k] for k in leaves)
    gaps = []
    for k in leaves:
        scale = max(reference[k], floor)
        gap = abs(program[k] - reference[k]) / scale if scale > 0 else math.inf
        gaps.append((gap if math.isfinite(gap) else math.inf, k))
    worst, at = max(gaps)
    return worst, at, statistics.median(g for g, _ in gaps)


def compare(program, reference, names=NUMBERS):
    """number -> (value, where it is worst) for those of ``NUMBERS`` that
    ``names`` holds (a cell's limits) and that have something to read: a
    family without running statistics may hand in an empty ``stats_norms``.

    The worst leaf is a widest gap and swings: in bfloat16 a few BatchNorm
    scales and shifts of the first stage (64 to 256 elements each) read 0.2
    to 0.3 from the float32 reference on every seed, in the program and in
    an independent bfloat16 spelling of the reference alike (PERF.md), so
    the median leaf stands beside it, steady from seed to seed."""
    loss_gap, at = 0.0, None
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(p - r) / abs(r)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= loss_gap:
            loss_gap, at = gap, "step%d" % (i + 1)
    out = {"loss_gap": (loss_gap, at)}
    grads = reference["grad_norms"]
    floor = NOUGHT_GRADIENT * statistics.median(grads.values())
    moved = sorted(k for k in grads if grads[k] >= floor)
    for name, key, leaves in (
            ("grad_norm_gap", "grad_norms", moved),
            ("update_norm_gap", "update_norms", moved),
            ("stats_norm_gap", "stats_norms",
             sorted(reference["stats_norms"]))):
        if not leaves:
            continue
        worst, at, median = _leaf_gaps(program[key], reference[key], leaves)
        out[name] = (worst, at)
        if name + "_median" in NUMBERS:
            out[name + "_median"] = (median, "median leaf")
    return {k: v for k, v in out.items() if k in names}


def verdict(numbers, limits):
    """(correct, [{"name", "value", "limit", "at"}]): every number beside
    its limit, in a fixed order."""
    rows, correct = [], True
    for name in NUMBERS:
        if name not in limits:
            continue
        # a number the cell's limits name and the readings do not give
        # (an empty ``stats_norms`` under a limit on ``stats_norm_gap``)
        # has not been shown to be under its limit
        value, at = numbers.get(name, (math.inf, "nothing to compare"))
        limit = limits[name]
        if not value <= limit:
            correct = False
        if not math.isfinite(value):
            value = 1e30            # JSON has no infinity
        rows.append({"name": name, "value": value, "limit": limit, "at": at})
    return correct, rows
