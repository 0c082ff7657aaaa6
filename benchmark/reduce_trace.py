"""From a profiler trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``)
to the numbers the per-layer metrics read.  Kept with the benchmark so that
every PR computes the same number the same way.

What a TPU trace looks like (looked at by hand, v5e, jax 0.9.0, PR 24):
planes ``/device:TPU:<n>`` hold the lines ``XLA Modules`` (one event per run
of a compiled program), ``XLA Ops`` (one event per HLO instruction; a
``while`` spans its body's events, so events nest) and ``Steps``; the plane
``/host:CPU`` holds one line per host thread with jax's own TraceMe events
and the program's ``TraceAnnotation`` spans.

* busy: union of the ``XLA Ops`` intervals of a device (falls back to ``XLA
  Modules`` where a trace has no op line), mean over the devices used.
* per-name device time: an event's SELF time (its duration less its direct
  children's), so a ``while`` is not counted on top of its body.
* a kernel's time: an op-line event's name is the HLO instruction's text, and
  a Pallas kernel shows there as ``custom-call(...)`` with
  ``custom_call_target="tpu_custom_call"`` and nothing of the kernel's own
  name (``pallas_call`` is given none, and no stat carries it).  So a kernel
  is told by its signature — how many operands the call takes and how many
  results it gives — AND by the shape of its first result, which for an
  attention kernel is the traffic's own [batch x heads, length, head size]:
  another Pallas call of the same arity is not summed in.  Summed self time
  of the events that match; a reader whose kernel the program runs by default
  and that matches nothing fails the run (``layer_metrics/flash_fwd_roofline.py``).
* exposed collective time: self time of collective instructions.  A TPU core
  runs one instruction of the line at a time, so what a collective (or the
  ``-start``/``-done`` halves of an asynchronous one) occupies on the op line
  is time in which no compute instruction runs on that device.
* idle gaps: the stretches between ``XLA Modules`` events, each named by the
  host event that overlaps it most (the narrowest wins a tie).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast")
# the host's own bookkeeping threads say nothing about what the program did
HOST_LINES_SKIPPED = ("tf_", "profiler", "ProfilerSession")


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    end: int  # ns
    self_ns: int = 0


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over devices
    busy_s_by_device: dict
    op_self_s: dict  # device -> {event name: seconds}
    ops: dict  # device -> [Event] of the op line, self time filled in
    modules: dict  # device -> [Event]
    host: list  # [Event] of every host thread
    n_devices: int

    def kernel_seconds(self, signatures: tuple, shape: tuple | None = None) -> float:
        """Summed self time of the Pallas custom calls whose (operands,
        results) is one of ``signatures`` and, where ``shape`` is given, whose
        first result has that shape (``shape_matches``), mean over devices;
        0.0 where nothing matches."""
        def hit(hlo):
            return (custom_call_signature(hlo) in signatures
                    and (shape is None or shape_matches(result_shapes(hlo)[0], shape)))

        per = [sum(e.self_ns for e in evs if hit(e.name)) / 1e9 for evs in self.ops.values()]
        return sum(per) / max(len(per), 1)

    def pallas_calls(self) -> dict:
        """{label: calls} of every Pallas custom call in the trace, for the
        message of a reader that found its kernel missing."""
        seen = defaultdict(int)
        for evs in self.ops.values():
            for e in evs:
                sig = custom_call_signature(e.name)
                if sig is not None:
                    seen[f"{sig[0]} operands {sig[1]} results {result_shapes(e.name)}"] += 1
        return dict(seen)

    def collective_seconds_by_device(self) -> dict:
        return {d: sum(e.self_ns for e in evs if _is_collective(e.name)) / 1e9
                for d, evs in self.ops.items()}

    def top_ops(self, k: int = 10) -> list:
        total = defaultdict(float)
        for per in self.op_self_s.values():
            for name, s in per.items():
                total[op_label(name)] += s / max(self.n_devices, 1)
        return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10, min_gap_s: float = 1e-4) -> list:
        """[[host event name, idle seconds], ...] on the first device."""
        if not self.modules:
            return []
        device = sorted(self.modules)[0]
        spans = _union([(e.start, e.end) for e in self.modules[device]])
        total = defaultdict(float)
        for (a0, a1), (b0, _) in zip(spans, spans[1:]):
            if (b0 - a1) / 1e9 < min_gap_s:
                continue
            total[_host_name(self.host, a1, b0)] += (b0 - a1) / 1e9
        return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def custom_call_signature(hlo: str):
    """(operands, results) of a ``tpu_custom_call`` instruction's text, else None."""
    if 'custom_call_target="tpu_custom_call"' not in hlo or " custom-call(" not in hlo:
        return None
    head, _, rest = hlo.partition(" custom-call(")
    operands = rest.split("), custom_call_target")[0]
    results = head.split("=", 1)[1]
    # every operand is a %name; a tuple of results lists one shape per result
    return operands.count("%"), max(1, results.count("]{"))


def result_shapes(hlo: str) -> list:
    """The dimensions of each result of an instruction's text, e.g.
    ``%x = (bf16[4,256,128]{...}, f32[4,1,256]{...}) custom-call(`` ->
    [(4, 256, 128), (4, 1, 256)]."""
    results = hlo.partition(" = ")[2].partition(" custom-call(")[0]
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"[a-z]+[0-9]*\[([0-9,]*)\]\{", results)]


def shape_matches(dims: tuple, shape: tuple) -> bool:
    """``shape`` is (batch x heads, length, head size).  A kernel may keep
    batch and heads as one axis or two, so the leading axes are compared by
    their product and the last two one by one."""
    lead = 1
    for d in dims[:-2]:
        lead *= d
    return len(dims) >= 3 and lead == shape[0] and tuple(dims[-2:]) == tuple(shape[1:])


def opcode(hlo: str) -> str:
    """``%name = shape opcode(operands) ...`` -> opcode ('' if it is no such text)."""
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", hlo.partition(" = ")[2])
    return m.group(1) if m else ""


def op_label(hlo: str) -> str:
    """A short name for an instruction's text: its name, what it is, what it gives."""
    name, _, rest = hlo.partition(" = ")
    sig = custom_call_signature(hlo)
    if sig is not None:
        return f"{name} pallas custom-call {sig[0]} operands {sig[1]} results"
    return f"{name} {opcode(hlo)} {rest[:60]}" if rest else name[:120]


def _is_collective(hlo: str) -> bool:
    return opcode(hlo).startswith(COLLECTIVES)


def _union(spans: list) -> list:
    out = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _fill_self_time(events: list) -> None:
    """Self time = duration less direct children's, by nesting of intervals."""
    events.sort(key=lambda e: (e.start, -(e.end - e.start)))
    stack: list[Event] = []
    for e in events:
        e.self_ns = e.end - e.start
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            stack[-1].self_ns -= e.end - e.start
        stack.append(e)
    for e in events:
        e.self_ns = max(e.self_ns, 0)


def _host_name(host: list, a: int, b: int) -> str:
    best, best_key = "(no host event)", (0, 0)
    for e in host:
        overlap = min(e.end, b) - max(e.start, a)
        if overlap <= 0:
            continue
        key = (overlap, -(e.end - e.start))
        if key > best_key:
            best, best_key = e.name, key
    return best


def _events(line) -> list:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append(Event(ev.name, start, start + int(ev.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(xplane_path: str, window_ns: tuple | None = None) -> TraceSummary:
    """``window_ns``: (start, end) on the trace's clock to clip to; default is
    from the first to the last device event."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            device = int(plane.name[len(DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[device] = _events(line)
                elif line.name == MODULE_LINE:
                    modules[device] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith(HOST_LINES_SKIPPED):
                    host.extend(_events(line))
    # a device that ran nothing in the window is not one of the cell's devices
    ops = {d: e for d, e in ops.items() if e}
    modules = {d: e for d, e in modules.items() if e}
    busy_from = ops or modules
    if not busy_from:
        return TraceSummary(0.0, 0.0, {}, {}, {}, {}, host, 0)
    if window_ns is None:
        window_ns = (min(e.start for evs in busy_from.values() for e in evs),
                     max(e.end for evs in busy_from.values() for e in evs))
    lo, hi = window_ns
    busy, op_self = {}, {}
    for device, evs in busy_from.items():
        spans = _union([(max(e.start, lo), min(e.end, hi)) for e in evs
                        if e.end > lo and e.start < hi])
        busy[device] = sum(b - a for a, b in spans) / 1e9
    for device, evs in ops.items():
        _fill_self_time(evs)
        per = defaultdict(float)
        for e in evs:
            per[e.name] += e.self_ns / 1e9
        op_self[device] = dict(per)
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy.values()) / len(busy),
        busy_s_by_device=busy, op_self_s=op_self, ops=ops, modules=modules,
        host=host, n_devices=len(busy))
