"""Device time by scope from the table the PROGRAM hands out.

``XLASimulator.round_scopes()`` gives {instruction name: op_name} of its compiled
round (made on the first request; ``--trace 0`` never asks), and
``fedml_tpu.core.obs.scopes`` holds the parsing, the SELF-time rule and the one
order of the vocabulary; this file only brings a run's trace to them, so every
cell whose driver keeps its simulator as ``driver.sim`` reads by scope, the
``dsllm7b-sim`` ones included.  On a program that has neither (a parent commit
before PR 35) every reader here returns None.

``device_ms_per_round(ctx, scope)``: self time of the op events whose op_name
holds ``scope`` (a substring, as ``benchmark/scope_times.py`` reads), mean over
the cell's devices, a round.  ``unscoped_ms_per_round(ctx)``: what no scope of the
vocabulary names; it also prints the cell's disjoint table to stderr.
"""

from __future__ import annotations

import sys
import time

try:
    from fedml_tpu.core.obs import scopes
except ImportError:  # the program is older than its scope table
    scopes = None


def by_op_name(ctx) -> dict | None:
    """{op_name: self seconds, mean over the cell's devices} of the traced
    window (``None``: instructions the table does not name); made once a run."""
    if not hasattr(ctx, "_seconds_by_op_name"):
        ctx._seconds_by_op_name = _by_op_name(ctx)
    return ctx._seconds_by_op_name


def _by_op_name(ctx) -> dict | None:
    round_scopes = getattr(getattr(ctx.driver, "sim", None), "round_scopes", None)
    if scopes is None or round_scopes is None or ctx.trace is None or not ctx.trace.ops \
            or not ctx.units:
        return None
    t0 = time.time()
    table = round_scopes()
    print(f"round_scopes(): {time.time() - t0:.2f} s, "
          f"{len(table) if table else 0} instructions", file=sys.stderr)
    if not table:
        return None
    total: dict = {}
    for events in ctx.trace.ops.values():
        per = scopes.op_name_seconds(((e.name, e.start, e.end - e.start) for e in events), table)
        for op_name, seconds in per.items():
            total[op_name] = total.get(op_name, 0.0) + seconds / len(ctx.trace.ops)
    return total


def device_ms_per_round(ctx, scope: str) -> float | None:
    seconds = by_op_name(ctx)
    if seconds is None:
        return None
    found = sum(s for op_name, s in seconds.items() if op_name is not None and scope in op_name)
    return 1000.0 * found / len(ctx.units) if found > 0.0 else None


def unscoped_ms_per_round(ctx) -> float | None:
    seconds = by_op_name(ctx)
    if seconds is None:
        return None
    rounds = len(ctx.units)
    rows = scopes.round_table(seconds)
    busy = sum(s for _, s in rows)
    print(f"device time by scope, ms a round ({rounds} rounds; the rows are disjoint, first match "
          f"in this order; op line busy {1000.0 * ctx.trace.busy_s / rounds:.1f}):", file=sys.stderr)
    for row, s in rows:
        if s > 0.0:
            print(f"  {row:34s} {1000.0 * s / rounds:10.2f}  {100.0 * s / busy:6.2f} %",
                  file=sys.stderr)
    print(f"  {'sum':34s} {1000.0 * busy / rounds:10.2f}", file=sys.stderr)
    for row in (scopes.STEP_ALONE, scopes.OUTSIDE):
        for op_name, s in scopes.largest(seconds, row):
            print(f"  largest of {row}: {1000.0 * s / rounds:9.2f}  {op_name}", file=sys.stderr)
    return 1000.0 * scopes.unscoped_seconds(seconds) / rounds
