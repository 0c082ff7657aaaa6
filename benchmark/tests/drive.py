"""The rest of a run without the look for a chip, at the tiny preset:

    JAX_PLATFORMS=cpu python benchmark/tests/drive.py --workload tiny.sim [--fault NAME] [--trace 1]

Prints what ``benchmark/run.py`` prints.  Four virtual devices for
``tiny.sim4``: XLA_FLAGS=--xla_force_host_platform_device_count=4."""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", default="2147483655")
    a = ap.parse_args()
    from benchmark import run
    from benchmark.tests.faults import FAULTS

    # Pallas kernels are not on this path off the chip: the program's default
    # attention resolves to its fused-XLA form on the CPU backend
    return run.run_cell(
        ["--workload", a.workload, "--seed", a.seed, "--seconds", "1", "--trace", a.trace,
         "--benchmark-json", os.path.join(HERE, "tiny_benchmark.json")],
        require_chip=False, sabotage=FAULTS[a.fault] if a.fault else None)


if __name__ == "__main__":
    sys.exit(main())
