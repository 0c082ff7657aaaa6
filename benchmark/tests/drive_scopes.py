"""``drive.py`` for ``tiny_benchmark_scopes.json``: the rest of a run without the
look for a chip, at the tiny preset, with the ten readers that take their table
from the program's ``XLASimulator.round_scopes()``:

    JAX_PLATFORMS=cpu python benchmark/tests/drive_scopes.py [--trace 1]

Prints what ``benchmark/run.py`` prints and, last on stderr, how often the run
asked the simulator for its table (``round_scopes() calls: <n>``)."""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", default="2147483655")
    a = ap.parse_args()
    from benchmark import run
    from fedml_tpu.simulation.xla.fed_sim import XLASimulator

    calls, asked = [], getattr(XLASimulator, "round_scopes", None)
    if asked is not None:  # a program older than PR 35 hands out no table

        def counted(self):
            calls.append(1)
            return asked(self)

        XLASimulator.round_scopes = counted
    code = run.run_cell(
        ["--workload", "tiny.scopes", "--seed", a.seed, "--seconds", "1", "--trace", a.trace,
         "--benchmark-json", os.path.join(HERE, "tiny_benchmark_scopes.json")],
        require_chip=False)
    print(f"round_scopes() calls: {len(calls)}", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main())
