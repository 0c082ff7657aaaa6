"""The rest of a run without the look for a chip, at the tiny ``nemotron_h`` preset
(``tiny_benchmark_nemotron_h.json``: a rehearsal of ``sim.fedavg.nemotron-nano.1chip``):

    JAX_PLATFORMS=cpu python benchmark/tests/drive_nemotron_h.py [--fault NAME] [--trace 1]

Prints what ``benchmark/run.py`` prints.  The faults are ``drive_kimi_linear.py``'s and
``faults.py``'s: this round program too returns its counters beside the loss."""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", default="2147483655")
    a = ap.parse_args()
    from benchmark import run
    from benchmark.tests.drive_kimi_linear import sim_state_unchanged
    from benchmark.tests.faults import sim_half_batch

    faults = {"sim_state_unchanged": sim_state_unchanged, "sim_half_batch": sim_half_batch}
    return run.run_cell(
        ["--workload", "tiny.nemotron", "--seed", a.seed, "--seconds", "1", "--trace", a.trace,
         "--benchmark-json", os.path.join(HERE, "tiny_benchmark_nemotron_h.json")],
        require_chip=False, sabotage=faults[a.fault] if a.fault else None)


if __name__ == "__main__":
    sys.exit(main())
