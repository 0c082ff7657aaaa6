"""What a ``reduce_trace.TraceSummary`` says about the phases of the in-mesh
round, for the ``idle.*`` and ``round.*_device_ms`` readers (a shared helper,
so that the four ``idle.*`` metrics are cut from one split and add up).

**Idle by host phase.**  The program's ``XLASimulator`` writes its host
phases into the profiler's trace as ``TraceAnnotation`` spans, on the device
trace's clock: ``sim.train`` around one ``train()`` call, and inside it, for
every round, ``round.select``, ``round.pack``, ``round.dispatch``,
``round.wait`` and ``round.close`` one after the other.  A device's idle time
is the window less the union of its op intervals (as ``device.idle_share``
takes it); the window here runs from the first to the last of those spans,
or of the device's events where they reach further.  Every stretch of idle
time is shared among the phases by the length of its overlap with each:

* ``prep``: under ``round.select`` or ``round.pack``;
* ``dispatch``: under ``round.dispatch``;
* ``between_rounds``: from the end of a ``round.wait`` to the start of the
  next ``round.select`` (``round.close``, ``train()``'s tail and preamble, the
  caller), and the window's two ends outside the first and the last of them;
* ``unattributed``: the rest — idle under ``round.wait`` (a stall inside the
  program) and, in a trace that has none of the spans, everything.

Idle time inside a module counts like idle time between modules, and the
four parts add up to the window's idle exactly.

**Device time by structure.**  An op-line event's name is the HLO
instruction's text and carries no scope (looked at on the v5e, PR 25: the
``fed.*`` ``jax.named_scope`` names reach XProf through the HLO metadata,
not the op line), so the phases of the compiled round are told by the
nesting the op line does show: a round module is a module whose ops hold a
``while`` with ``conditional`` events inside it — the stream of local steps
and its client-boundary branch (``ml/engine/packed.py``).  The flushes are
those ``conditional`` events (the outermost, whole duration: both branches,
so the cost of having the branch is in it); the server step is what runs in
that module after the ``while`` has ended, less collective instructions
(they are ``collective.exposed_ms_per_round``'s), by self time.
"""

from __future__ import annotations

from benchmark import reduce_trace

PREP, DISPATCH, WAIT = ("round.select", "round.pack"), ("round.dispatch",), ("round.wait",)
SPANS = PREP + DISPATCH + WAIT + ("round.close", "round", "sim.train")
PARTS = ("prep", "dispatch", "between_rounds", "unattributed")


def _named(host: list, names: tuple) -> list:
    return sorted((e.start, e.end) for e in host if e.name in names)


def _overlap(a0: int, a1: int, spans: list) -> int:
    return sum(max(0, min(a1, b1) - max(a0, b0)) for b0, b1 in spans)


def window_ns(trace) -> tuple:
    """From the first to the last of the program's spans and the devices' events."""
    events = [e for evs in (trace.ops or trace.modules).values() for e in evs]
    events += [e for e in trace.host if e.name in SPANS]
    return min(e.start for e in events), max(e.end for e in events)


def idle_split(trace) -> dict | None:
    """{part: idle seconds, mean over the devices} for the four ``PARTS``,
    plus ``window_s``; None where the trace holds no device events."""
    cached = getattr(trace, "_idle_split", None)
    if cached is not None:
        return cached
    per_device = trace.ops or trace.modules
    if not per_device:
        return None
    lo, hi = window_ns(trace)
    prep, dispatch = _named(trace.host, PREP), _named(trace.host, DISPATCH)
    selects, waits = _named(trace.host, PREP[:1]), _named(trace.host, WAIT)
    between = []
    if selects and waits:
        between.append((lo, selects[0][0]))
        for _, end in waits:
            nxt = next((s for s, _ in selects if s >= end), hi)
            between.append((end, nxt))
    total = dict.fromkeys(PARTS, 0.0)
    for evs in per_device.values():
        busy = reduce_trace._union([(max(e.start, lo), min(e.end, hi)) for e in evs])
        edges = [lo] + [t for span in busy for t in span] + [hi]
        for a0, a1 in zip(edges[::2], edges[1::2]):  # the gaps between busy stretches
            if a1 <= a0:
                continue
            shares = {"prep": _overlap(a0, a1, prep), "dispatch": _overlap(a0, a1, dispatch),
                      "between_rounds": _overlap(a0, a1, between)}
            shares["unattributed"] = (a1 - a0) - sum(shares.values())
            for part, ns in shares.items():
                total[part] += ns / 1e9
    out = {part: s / len(per_device) for part, s in total.items()}
    out["window_s"] = (hi - lo) / 1e9
    trace._idle_split = out
    return out


def _inside(inner, outer) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end and inner is not outer


def round_split(trace) -> dict | None:
    """{"rounds": round modules a device ran, "flush_s", "server_step_s":
    device seconds of all of them, mean over the devices}; None where no
    module of the trace holds a ``while`` with a ``conditional`` inside."""
    cached = getattr(trace, "_round_split", None)
    if cached is not None:
        return cached
    found = []  # (round modules, flush ns, server step ns) of each device that ran a round
    for device, modules in trace.modules.items():
        ops = trace.ops.get(device, [])
        opcodes = [reduce_trace.opcode(e.name) for e in ops]
        whiles = [e for e, code in zip(ops, opcodes) if code == "while"]
        conds = [e for e, code in zip(ops, opcodes) if code == "conditional"]
        conds = [c for c in conds if not any(_inside(c, o) for o in conds)]  # the outermost
        n = flush_ns = server_ns = 0
        for m in modules:
            loops = [w for w in whiles if m.start <= w.start < m.end
                     and not any(_inside(w, o) for o in whiles)]
            inner = [c for c in conds if any(_inside(c, w) for w in loops)]
            if not inner:
                continue
            n += 1
            flush_ns += sum(c.end - c.start for c in inner)
            after = max(w.end for w in loops)
            server_ns += sum(e.self_ns for e in ops if after <= e.start < m.end
                             and not reduce_trace._is_collective(e.name))
        if n:
            found.append((n, flush_ns / 1e9, server_ns / 1e9))
    if not found:
        return None
    rounds, flush_s, server_s = (sum(column) / len(found) for column in zip(*found))
    out = {"rounds": rounds, "flush_s": flush_s, "server_step_s": server_s}
    trace._round_split = out
    return out


def idle_ms_per_round(ctx, part: str):
    """One ``idle.*`` metric: milliseconds of a device's idle time a round
    that fall to ``part``."""
    if ctx.trace is None or not ctx.units:
        return None
    split = idle_split(ctx.trace)
    return None if split is None else 1000.0 * split[part] / len(ctx.units)


def device_ms_per_round(ctx, key: str, what: str):
    """One ``round.*_device_ms`` metric.  Silent where the round has no such
    phase; where the simulator is packed and the trace shows none, the run
    fails: a phase that runs and is no longer read is this reader's fault."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    split = round_split(ctx.trace)
    if split is None or split[key] <= 0.0:
        if getattr(getattr(ctx.driver, "sim", None), "packed", False):
            raise RuntimeError(
                f"the simulator runs the packed round and the trace shows no {what}: no "
                f"module with a while that holds conditionals, or nothing after it; modules "
                f"{sorted({m.name for ms in ctx.trace.modules.values() for m in ms})[:8]}")
        return None
    return 1000.0 * split[key] / split["rounds"]
