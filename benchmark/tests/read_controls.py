"""Read, on the chip and at a cell's own size, what the control and the
planted faults give the comparison — the upper readings a limit is set below.

    python3 benchmark/tests/read_controls.py --workloads sim.fedavg.1chip sim.fedavg.4chip --seeds 11 12 13

The reference is one device's code, so one chip reads every cell: cells of one
configuration whose traffic differs only in its limits share the readings, and
each is judged by its own limits.  For each seed the plain reference follows the cell's first units once at
``highest`` (the truth), then again in the program's place: one precision
below what the configuration states (the control: ``int8`` and ``float8`` for
``bfloat16``, the smaller reading of the two counts), and at the
configuration's own precision with a fault planted — half of
every batch left out, and for a cell on four chips the exchange left out.
Every reading goes through ``benchmark/compare.py`` and is judged against the
cell's own limits, as a run's are: each case prints ``correct``, and the script
exits non-zero if a control or a planted fault comes out correct (or ``sound``,
the reference in the configuration's own arithmetic, read beside them as what
the stated precision gives when it is not the program's, does not).  A step that returns its state
unchanged reads 1 by that measure and needs no run.  Not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# what the configuration states -> (the precisions one step below it, its own arithmetic)
BELOW = {"bfloat16": (("int8", "float8"), "bfloat16"), "float32": (("bfloat16",), "highest")}


def cases(cell) -> list:
    """(name, precision, fault) of every reading of a cell; only ``sound`` is to pass."""
    below, own = BELOW[cell.model["compute_dtype"]]
    out = [("control_" + p, p, None) for p in below]
    out += [("sound", own, None), ("fault_half_batch", own, "half_batch")]
    if cell.chips > 1:
        out.append(("fault_no_exchange", own, "no_exchange"))
    return out


NOT_THE_WORK = ("limits", "why", "who", "source", "assumed")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--require-chip", type=int, default=1)
    a = ap.parse_args()
    import jax

    from benchmark import compare, peaks, run

    if a.require_chip:
        peaks.peaks_for(jax.devices()[0].device_kind)
    cells = [run.load_cell(a.benchmark_json, w) for w in a.workloads]
    passed_wrongly = []
    for seed in a.seeds:
        read = {}  # the work (configuration and traffic less its limits) -> {case: numbers}
        for cell in cells:
            work = json.dumps([cell.cell["config"], {k: v for k, v in cell.traffic.items()
                                                     if k not in NOT_THE_WORK}], sort_keys=True)
            got = read.setdefault(work, {})
            driver = importlib.import_module("benchmark.drivers." + cell.traffic["driver"]).Driver(
                cell.model, cell.traffic, seed, cell.chips, jax.devices()[0].platform)
            line = {"workload": cell.name, "seed": seed, "limits": cell.traffic["limits"]}
            for name, precision, fault in cases(cell):
                if name not in got:
                    if "truth" not in got:
                        t = time.time()
                        got["truth"] = driver.reference_readings("highest")
                        line["reference_s"] = round(time.time() - t, 1)
                    got[name] = compare.numbers(
                        driver.reference_readings(precision, fault), got["truth"])
                correct, table = compare.judge(got[name], cell.traffic["limits"])
                line[name] = {"correct": correct, **{k: v["value"] for k, v in table.items()}}
                if correct != (name == "sound"):
                    passed_wrongly.append((cell.name, seed, name))
            print(json.dumps(line), flush=True)
    if passed_wrongly:
        print(f"judged wrongly against the cell's limits: {passed_wrongly}", file=sys.stderr)
    return 1 if passed_wrongly else 0


if __name__ == "__main__":
    sys.exit(main())
