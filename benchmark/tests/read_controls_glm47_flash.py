"""``read_controls.py`` for the ``glm4_moe_lite`` cells, with this configuration's own
planted fault beside the control and the half batch: the reference with
``mtp_loss_weight`` 0 (``fault="no_mtp"``: ``L_main`` alone is trained).  It has to come
out not correct, or the comparison cannot see the prediction module.  At batch 1 the
half batch is left out: half of one row is no row, the reading is 1 by construction and
costs a compile and a followed round on the chip.

    python3 benchmark/tests/read_controls_glm47_flash.py --workloads sim.fedavg.glm47-flash.1chip --seeds 11
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.tests import read_controls  # noqa: E402

_cases = read_controls.cases


def cases(cell) -> list:
    _, own = read_controls.BELOW[cell.model["compute_dtype"]]
    kept = [c for c in _cases(cell)
            if c[2] != "half_batch" or int(cell.traffic["batch_sequences"]) > 1]
    return kept + [("fault_no_mtp", own, "no_mtp")]


if __name__ == "__main__":
    read_controls.cases = cases
    sys.exit(read_controls.main())
