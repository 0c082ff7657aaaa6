"""The comparison that decides ``correct``.

A driver hands over two sets of readings of the same shape, the program's
and the plain reference's, one entry per followed unit (a round, a pass):

    {"loss": [l_0, ...], "change": [{leaf: ||W_{i+1} - W_0||}, ...],
     "sketch": [{leaf: <signs, W_{i+1} - W_0>}, ...]}

Numbers compared, each against a limit of its own from the traffic file:

* ``loss_gap.u<i>`` — |program - reference| / reference of unit i's mean loss.
* ``change_gap.u<i>`` — worst leaf of the gap between the two NORMS of the
  parameters' change since the start (not the norm of their difference),
  over the reference's norm of that leaf or of the median leaf, whichever is
  larger.  Unit 0's is the first update as the optimizer's consumer gets it
  (for FedAvg: the cohort's mean delta the server step applies).  Leaves the
  reference moves by under a thousandth of the median leaf are left out:
  their change is rounding.  A leaf left where it was reads 1.
* ``direction_gap.u<i>`` — the gap of norms is blind to rounding noise (it adds
  in quadrature: a relative error e of the update moves its norm by e*e/2), so
  the arithmetic's precision is read from the update's DIFFERENCE instead,
  without holding either side's tensors: each side sums its change under the
  same fixed patterns of random signs, 16 numbers a leaf
  (``reference.leaf_readings``).  The number is the root of (the squared
  differences of the two sides' sketches, summed over the leaves) over (16 x the
  reference's squared norms, summed over the leaves): an estimate of
  ||program's update - reference's|| / ||reference's|| over the whole model.
"""

from __future__ import annotations

import math
import statistics

DEAD_LEAF = 1e-3  # of the median leaf's change, in the reference


def change_gap(program: dict, reference: dict) -> tuple[float, str]:
    median = statistics.median(reference.values())
    worst, at = 0.0, ""
    for leaf, ref in reference.items():
        if ref < DEAD_LEAF * median:
            continue
        gap = abs(program[leaf] - ref) / max(ref, median)
        if not gap <= worst:  # a NaN is the worst there is
            worst, at = gap, leaf
    return worst, at


def direction_gap(program: dict, reference: dict, norms: dict) -> dict:
    """Whole-model relative difference of the two updates, from their sketches;
    ``at`` names the leaf that gives most of it."""
    per_leaf = {leaf: sum((p - r) ** 2 for p, r in zip(program[leaf], reference[leaf]))
                / len(reference[leaf]) for leaf in reference}
    total = sum(n * n for n in norms.values())
    at = max(per_leaf, key=per_leaf.get)
    return {"value": math.sqrt(sum(per_leaf.values()) / total), "at": at,
            "program": math.sqrt(per_leaf[at]), "reference": norms[at]}


def numbers(program: dict, reference: dict) -> dict:
    """{name: {"value": v, "at": leaf or ""}} for every number compared."""
    out = {}
    for i, (p, r) in enumerate(zip(program["loss"], reference["loss"])):
        out[f"loss_gap.u{i}"] = {"value": abs(p - r) / abs(r), "at": "",
                                 "program": p, "reference": r}
    for i, (p, r) in enumerate(zip(program["change"], reference["change"])):
        value, at = change_gap(p, r)
        out[f"change_gap.u{i}"] = {"value": value, "at": at,
                                   "program": p.get(at), "reference": r.get(at)}
    for i, (p, r, norms) in enumerate(zip(program["sketch"], reference["sketch"],
                                          reference["change"])):
        out[f"direction_gap.u{i}"] = direction_gap(p, r, norms)
    if len(out) != 3 * len(reference["loss"]) or not out:
        raise ValueError("program and reference followed different units")
    return out


def judge(compared: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct only if every limit holds.  A
    limit is keyed by the number's name, else by its kind (the part before
    ``.u``).  A number the traffic file gives no limit has no upper reading
    (PERF.md says which and why): it is printed and not compared."""
    ok, table = True, {}
    for name, entry in compared.items():
        limit = limits.get(name, limits.get(name.split(".u")[0]))
        value = entry["value"]
        if limit is None:
            table[name] = {"value": value, "limit": None}
            continue
        holds = math.isfinite(value) and value <= float(limit)
        ok = ok and holds
        table[name] = {"value": value, "limit": float(limit)}
    if not any(t["limit"] is not None for t in table.values()):
        raise ValueError("no number has a limit: nothing would be compared")
    return ok, table
