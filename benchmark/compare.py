"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside a limit of its own
(``benchmark/limits/<workload>.json`` holds the limits and the readings
they were set from).
"""

import json
import math
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(workload):
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        return json.load(f)["numbers"]


def worst_leaf_gap(observed, reference, skip=()):
    """Largest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger. -> (gap, leaf)"""
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        if leaf in skip:
            continue
        got = observed.get(leaf, float("nan"))
        gap = abs(got - ref) / max(ref, median)
        if not gap <= worst:            # NaN counts as the worst
            worst, where = gap, leaf
    return worst, where


def unmoved_by_rule(reference_grad_norms):
    """Leaves whose gradient is nought to rounding in the reference
    (under a thousandth of the median leaf's): Adam moves them by
    round-off alone, so their change is not compared."""
    median = statistics.median(reference_grad_norms.values())
    return {k for k, v in reference_grad_norms.items()
            if v < 1e-3 * median}


def train_numbers(observed, reference):
    """The numbers a training cell compares, {name: (value, note)}."""
    out = {}
    for i, (got, ref) in enumerate(zip(observed["losses"],
                                       reference["losses"])):
        out["loss%d" % (i + 1)] = (abs(got - ref) / abs(ref),
                                   "%.6f vs %.6f" % (got, ref))
    if len(observed["losses"]) != len(reference["losses"]):
        out["loss_steps"] = (float("nan"), "step counts differ")
    out["grad_norm"] = worst_leaf_gap(observed["grad_norms"],
                                      reference["grad_norms"])
    out["change_norm"] = worst_leaf_gap(
        observed["change_norms"], reference["change_norms"],
        skip=unmoved_by_rule(reference["grad_norms"]))
    return out


def judge(numbers, limits):
    """-> (correct, {name: {"value", "limit", "ok", "note"}}). A number
    without an entry in the limits, or a limit without its number, fails
    the check. An entry whose limit is null is a number that is read and
    printed but not compared (the entry says why)."""
    rows, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value, note = numbers.get(name, (float("nan"), "not produced"))
        entry = limits.get(name)
        limit = entry.get("limit") if entry else None
        if entry is not None and limit is None and name in numbers:
            good, note = True, note + "; not compared"
        else:
            good = limit is not None and math.isfinite(value) \
                and value <= limit
        ok = ok and good
        rows[name] = {"value": value, "limit": limit, "ok": good,
                      "note": note}
    return ok, rows


def print_rows(rows, out=sys.stderr):
    """Each number compared beside its limit, one a line."""
    for name, r in rows.items():
        print("compared %-18s %.6g  limit %s  %s  (%s)"
              % (name, r["value"], r["limit"],
                 "ok" if r["ok"] else "FAIL", r["note"]), file=out)
