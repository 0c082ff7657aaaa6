"""``read_controls.py`` for the ``nemotron_h`` cells, with this configuration's own planted
faults beside the controls, each read at the configuration's own precision: the
reference without the Mamba-2 skip ``D x`` (``fault="no_D"``), without the gate
``SiLU(z)`` before the grouped norm (``fault="no_gate"``), and with ``relu`` where the
experts have ``relu^2`` (``fault="relu"``).  Each has to come out not correct, or the
comparison cannot see that part of the layer.  At batch 1 the half batch is left out:
half of one row is no row, the reading is 1 by construction and costs a compile and a
followed round on the chip.  Beside each case's line, ``read_controls_sdar.py``'s
``leaves`` line says where its gaps sit.

    python3 benchmark/tests/read_controls_nemotron_h.py --workloads sim.fedavg.nemotron-nano.1chip --seeds 11
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import compare  # noqa: E402
from benchmark.tests import read_controls, read_controls_sdar  # noqa: E402

_cases = read_controls.cases


def cases(cell) -> list:
    _, own = read_controls.BELOW[cell.model["compute_dtype"]]
    kept = [c for c in _cases(cell)
            if c[2] != "half_batch" or int(cell.traffic["batch_sequences"]) > 1]
    out = kept + [("fault_no_D", own, "no_D"), ("fault_no_gate", own, "no_gate"),
                  ("fault_relu", own, "relu")]
    read_controls_sdar._pending[:] = [name for name, _, _ in out]
    return out


if __name__ == "__main__":
    read_controls.cases, compare.numbers = cases, read_controls_sdar.numbers
    sys.exit(read_controls.main())
