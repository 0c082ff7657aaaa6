"""The rest of a run without the look for a chip, at the tiny ``kimi_linear`` preset
(``tiny_benchmark_kimi_linear.json``: a rehearsal of ``sim.fedavg.kimi-linear.1chip``):

    JAX_PLATFORMS=cpu python benchmark/tests/drive_kimi_linear.py [--fault NAME] [--trace 1]

Prints what ``benchmark/run.py`` prints.  The faults are ``faults.py``'s, for a round
program that returns its counters beside the loss (five results, not four)."""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def sim_state_unchanged(driver):
    """The round returns the global model it was given."""
    real = driver.sim._round_fn

    def broken(variables, server_state, *rest):
        _, state, *others = real(variables, server_state, *rest)
        return (variables, state, *others)

    driver.sim._round_fn = broken


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", default=None)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", default="2147483655")
    a = ap.parse_args()
    from benchmark import run
    from benchmark.tests.faults import sim_half_batch

    faults = {"sim_state_unchanged": sim_state_unchanged, "sim_half_batch": sim_half_batch}
    return run.run_cell(
        ["--workload", "tiny.kimi", "--seed", a.seed, "--seconds", "1", "--trace", a.trace,
         "--benchmark-json", os.path.join(HERE, "tiny_benchmark_kimi_linear.json")],
        require_chip=False, sabotage=faults[a.fault] if a.fault else None)


if __name__ == "__main__":
    sys.exit(main())
