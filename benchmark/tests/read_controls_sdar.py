"""``read_controls.py`` for the ``sdar_moe`` cells, with this configuration's own planted
faults beside the controls: the reference in which a noised query sees the earlier
NOISED blocks in place of the clean ones (``fault="noised_context"``: the mask of a
different objective), and the reference without the ``1 / t_b`` weights
(``fault="no_weights"``).  Each has to come out not correct, or the comparison cannot see
the block-diffusion mask or the loss's weights.  At batch 1 the half batch is left out:
half of one row is no row, the reading is 1 by construction and costs a compile and a
followed round on the chip.

Beside each case's line, one ``leaves`` line says where its gaps sit: the leaf each
number names, the three leaves of the largest change gap, and the change gap over the
``router`` leaves alone and over every other leaf, so that the bfloat16 reference shows
how far its router moves from the float32 one.

    python3 benchmark/tests/read_controls_sdar.py --workloads sim.fedavg.sdar.1chip --seeds 11
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import json  # noqa: E402
import statistics  # noqa: E402

from benchmark import compare  # noqa: E402
from benchmark.tests import read_controls  # noqa: E402

_cases, _numbers = read_controls.cases, compare.numbers
_pending = []  # the names of the cases whose numbers are still to come, in order


def cases(cell) -> list:
    _, own = read_controls.BELOW[cell.model["compute_dtype"]]
    kept = [c for c in _cases(cell)
            if c[2] != "half_batch" or int(cell.traffic["batch_sequences"]) > 1]
    out = kept + [("fault_noised_context", own, "noised_context"),
                  ("fault_no_weights", own, "no_weights")]
    _pending[:] = [name for name, _, _ in out]
    return out


def leaf_gaps(program: dict, reference: dict) -> dict:
    """Every live leaf's change gap, as ``compare.change_gap`` reckons the worst."""
    median = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - ref) / max(ref, median)
            for leaf, ref in reference.items() if ref >= compare.DEAD_LEAF * median}


def numbers(program: dict, reference: dict) -> dict:
    out = _numbers(program, reference)
    gaps = leaf_gaps(program["change"][0], reference["change"][0])
    router = [g for leaf, g in gaps.items() if "router" in leaf]
    rest = [g for leaf, g in gaps.items() if "router" not in leaf]
    print(json.dumps({"leaves": _pending.pop(0) if _pending else "?",
                      "at": {k: v["at"] for k, v in out.items() if v["at"]},
                      "worst_change": sorted(gaps.items(), key=lambda x: -x[1])[:3],
                      "change_gap_router": max(router, default=None),
                      "change_gap_other": max(rest, default=None)}), flush=True)
    return out


if __name__ == "__main__":
    read_controls.cases, compare.numbers = cases, numbers
    sys.exit(read_controls.main())
