"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in ``BENCHMARK.json``, its configuration file (the entry's
``file``), its traffic file (``benchmark/traffic/<traffic>.json``, laid over its
``base`` where it names one), the driver
the traffic file names (``benchmark/drivers/<driver>.py``) and one reader per
metric (``benchmark/end_to_end/<metric>.py``; with ``--trace 1``
``benchmark/layer_metrics/<metric>.py``) — all by name.  A later PR adds a
cell, a configuration or a metric as files of its own plus ``BENCHMARK.json``
entries and edits nothing that is here.

A run: set-up (the program's own start-up, the seed's weights and token
shards, the first units, which compile or load the cell's programs and whose
results the reference is later held against) -> the window (whole units until
the next would end past ``--seconds``) -> the device's peak memory is read ->
the program's state is freed -> the plain reference follows the first units
-> every number compared is printed beside its limit -> one JSON line.

It fails, printing no result, off the chip: a device kind that is not in
``benchmark/peaks.py``, or another number of devices than the cell's ``chips``.
"""

from __future__ import annotations

_T0 = __import__("time").time()  # set-up is on the clock from here

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, flops, peaks, reduce_trace  # noqa: E402

def load_traffic(name: str) -> dict:
    """``benchmark/traffic/<name>.json``.  A file that names a ``base`` is that
    file with its own keys laid over it, ``limits`` key by key: a mix that
    differs from another in one limit or one parameter states only that."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        traffic = json.load(f)
    if "base" not in traffic:
        return traffic
    merged = load_traffic(traffic.pop("base"))
    limits = {**merged.get("limits", {}), **traffic.pop("limits", {})}
    merged.update(traffic, limits=limits)
    return merged


def load_cell(benchmark_json: str, workload: str) -> types.SimpleNamespace:
    with open(benchmark_json) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {benchmark_json}: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    # a config's file path is relative to the repository, wherever the json that names it lies
    with open(os.path.join(ROOT, config["file"])) as f:
        model = json.load(f)
    traffic = load_traffic(cell["traffic"])

    def reports(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return types.SimpleNamespace(
        name=workload, cell=cell, chips=int(cell["chips"]), model=model, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)])


def read_metric(kind: str, name: str, ctx) -> float | None:
    """``kind``: ``end_to_end`` or ``layer_metrics``, the directory of readers."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(kind + "_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


class CompileCounter:
    """Counts what jax compiles or traces while ``armed``: a warm-up that
    leaves one for the window is wrong."""

    EVENTS = ("backend_compile_duration", "jaxpr_trace_duration")

    def __init__(self):
        from jax import monitoring

        self.armed, self.seen = False, []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and str(event).endswith(self.EVENTS):
            self.seen.append((str(event), float(duration)))


def device_stamp(devices, trace=None, window_s=None) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    stamp = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace is not None:
        stamp["busy_s"], stamp["window_s"] = trace.busy_s, window_s
    return stamp


def run_window(driver, seconds: float, trace_dir: str | None):
    """Whole units until the next one would end past the deadline (at least
    one).  Returns (units, seconds from the window's start to the end of the
    last unit); stopping the profiler is not on that clock."""
    import jax

    units, walls = [], []
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    start = time.perf_counter()
    try:
        while True:
            t = time.perf_counter()
            unit = driver.run_unit()
            now = time.perf_counter()
            unit["wall_seconds"] = now - t
            units.append(unit)
            walls.append(unit["wall_seconds"])
            if now - start + statistics.median(walls) > seconds:
                break
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return units, now - start


def run_cell(argv=None, *, require_chip: bool = True, sabotage=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-json", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another list of cells (benchmark/tests use a tiny one)")
    a = ap.parse_args(argv)
    cell = load_cell(a.benchmark_json, a.workload)

    import jax

    devices = jax.devices()
    if require_chip:
        chip_peaks = peaks.peaks_for(devices[0].device_kind)  # raises off the chip
        if len(devices) != cell.chips:
            raise SystemExit(f"{len(devices)} devices here, cell {cell.name} asks for {cell.chips}")
        device_type = devices[0].platform
    else:  # benchmark/tests only: the rest of a run, on whatever is here
        chip_peaks = peaks.DEVICE_PEAKS["TPU v5 lite"]
        device_type = devices[0].platform
        devices = devices[: cell.chips]
    counter = CompileCounter()
    driver_mod = importlib.import_module("benchmark.drivers." + cell.traffic["driver"])
    driver = driver_mod.Driver(cell.model, cell.traffic, a.seed, cell.chips, device_type)
    marks = [("imports and the look for the chip", time.time())]
    driver.setup()
    marks.append(("the program's start-up, the seed's weights and shards", time.time()))
    if sabotage is not None:  # benchmark/tests plant a fault under the timed path here
        sabotage(driver)
    driver.first_units()
    marks.append((f"the first units ({driver.unit}) and their readings", time.time()))
    setup_compile_s = driver.compile_seconds()
    setup_s = time.time() - _T0
    print("set-up: " + "; ".join(f"{what} {t - t0:.1f} s" for (what, t), t0 in
                                 zip(marks, [_T0] + [t for _, t in marks])), file=sys.stderr)

    trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_") if a.trace else None
    counter.armed = True
    units, window_s = run_window(driver, a.seconds, trace_dir)
    counter.armed = False
    print(f"compilations or traces inside the window: {len(counter.seen)} {counter.seen[:4]}",
          file=sys.stderr)
    for u in units:  # every unit on the clock, for whoever reads a noisy run
        print("unit: " + ", ".join(f"{k} {v:.4f}" for k, v in u.items() if k.endswith("seconds")),
              file=sys.stderr)
    tokens = sum(u["tokens"] for u in units)
    failed = sum(1 for u in units if u["failed"])

    trace = None
    if trace_dir:
        try:
            trace = reduce_trace.reduce(reduce_trace.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stamp = device_stamp(devices, trace, window_s)

    ctx = types.SimpleNamespace(
        cell=cell, model=cell.model, traffic=cell.traffic, chips=cell.chips,
        peaks=chip_peaks, units=units, window_s=window_s, tokens=tokens,
        sequences=sum(u["sequences"] for u in units), trace=trace, driver=driver,
        device=stamp, setup={"setup_s": setup_s, "compile_s": setup_compile_s}, flops=flops)
    kind, wanted = ("layer_metrics", cell.per_layer) if a.trace else ("end_to_end", cell.end_to_end)
    metrics = {}
    for m in wanted:
        value = read_metric(kind, m["name"], ctx)
        if value is not None:  # a reader that finds nothing to read says nothing
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the reference runs last: the peak has been read, the program's state goes
    program = driver.program
    driver.release()
    gc.collect()
    jax.clear_caches()
    t_ref = time.time()
    compared = compare.numbers(program, driver.reference_readings())
    correct, table = compare.judge(compared, cell.traffic["limits"])
    correct = correct and failed == 0
    print(f"reference took {time.time() - t_ref:.1f} s; window {window_s:.2f} s, "
          f"{len(units)} x {driver.unit}; set-up {setup_s:.1f} s", file=sys.stderr)

    result = {"correct": bool(correct), "attempted": len(units), "failed": failed,
              "metrics": metrics, "device": stamp}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace.top_ops(10), "idle_gaps": trace.idle_gaps(10)}
    result["compilations_in_window"] = len(counter.seen)
    result["compared"] = table
    for name, entry in table.items():
        c = compared[name]
        limit = "none (not compared)" if entry["limit"] is None else f"{entry['limit']:.6g}"
        print(f"compared {name}: {entry['value']:.6g} limit {limit}"
              f" (program {c['program']:.8g}, reference {c['reference']:.8g}"
              + (f", worst leaf {c['at']})" if c["at"] else ")"), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run_cell())
