#!/usr/bin/env python
"""Offline round-trace reconstruction and critical-path reporting.

Reads the ``span_start`` / ``span_end`` / ``span_event`` records that
``fedml_tpu.core.obs`` emits through the mlops JSONL sink and rebuilds one
span tree per (run, round) trace:

* **Integrity** — every trace must have exactly one root span (the round),
  no span may reference a parent that never started, and every started
  span must close.  A crash-restarted server closes its predecessor's
  round span under the same deterministic id, so a clean recovery still
  reads as closed here.  ``--assert-closed`` turns violations into exit
  code 2 (the chaos gate).
* **Critical path** — walk from the round root to the leaf that closed
  last; the chain of spans on that walk is where the round's wall time
  went (the slowest silo's train+upload leg, a retransmit storm, ...).
* **Straggler ranking** — ``client.train`` spans sorted by duration;
  anything slower than ``--slow-factor`` x the round's median is flagged
  (the same factor ``obs_slow_round_factor`` uses online).
* **Async mode** — a trace whose round span carries an async ``mode`` (or
  any ``buffer.flush`` span) reports per-flush staleness distribution and
  buffer occupancy columns, and ranks stragglers by TIME-TO-REPORT (span
  close relative to the cycle open) instead of train duration: under
  buffered execution a slow client hurts by *when its delta lands*, not
  by how long its local step ran.
* **Per-client attribution** (``--clients``) — with the telemetry plane on,
  remote ``client.train`` sub-spans are grafted into the tree, so each
  participant gets a compute / network / deferred split: compute is the
  remote train span, network is the ``upload`` span's SELF time (duration
  minus nested server-side children), deferred is the async gap between
  the last report and the cycle open not explained by either.  The
  dominant phase is the participant's straggler class.
* **Run diff** (``--diff A B``) — compare two runs' per-phase attribution
  and critical-path wall time; phases whose mean self-time regressed past
  ``--diff-tolerance`` are printed and exit code 1.

Durations prefer the end record's monotonic ``duration_s``; adopted ends
(crash recovery) carry none and fall back to the sink wall-timestamp delta.

Usage::

    python tools/trace_report.py run.jsonl
    python tools/trace_report.py run.jsonl --round 3 --clients
    python tools/trace_report.py a.jsonl b.jsonl --assert-closed
    python tools/trace_report.py --diff before.jsonl after.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

SPAN_TOPICS = ("span_start", "span_end", "span_event")


def load_records(path: str) -> List[Dict[str, Any]]:
    """The file's span-topic records, in file order (other topics skipped;
    unparseable lines skipped — a torn tail write is not a trace error)."""
    out: List[Dict[str, Any]] = []
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("topic") in SPAN_TOPICS:
                out.append(rec)
    return out


# spans a run opens outside any round (the XLA simulator's start-up and its
# train() call): they share the run's round-less trace, every one a root
RUN_SPANS = ("sim.build", "sim.train")


class SpanNode:
    """One reconstructed span: paired start/end records plus events."""

    __slots__ = ("span_id", "start", "end", "events", "children")

    def __init__(self, span_id: str):
        self.span_id = span_id
        self.start: Optional[Dict[str, Any]] = None
        self.end: Optional[Dict[str, Any]] = None
        self.events: List[Dict[str, Any]] = []
        self.children: List["SpanNode"] = []

    @property
    def name(self) -> str:
        for rec in (self.start, self.end):
            if rec is not None and rec.get("name"):
                return str(rec["name"])
        return "?"

    @property
    def node(self) -> Any:
        return (self.start or {}).get("node", "?")

    @property
    def parent_span_id(self) -> Optional[str]:
        return (self.start or {}).get("parent_span_id")

    @property
    def round_idx(self) -> Optional[int]:
        for rec in (self.start, self.end):
            if rec is not None and "round_idx" in rec:
                return int(rec["round_idx"])
        return None

    def duration_s(self) -> float:
        """Monotonic duration when the closer measured one; wall-ts delta
        for cross-process (adopted) closes; 0 when unclosed."""
        if self.end is not None and isinstance(
                self.end.get("duration_s"), (int, float)):
            return float(self.end["duration_s"])
        if (self.start is not None and self.end is not None
                and isinstance(self.start.get("ts"), (int, float))
                and isinstance(self.end.get("ts"), (int, float))):
            return max(0.0, float(self.end["ts"]) - float(self.start["ts"]))
        return 0.0

    def end_ts(self) -> float:
        if self.end is not None and isinstance(self.end.get("ts"), (int, float)):
            return float(self.end["ts"])
        if self.start is not None and isinstance(self.start.get("ts"), (int, float)):
            return float(self.start["ts"]) + self.duration_s()
        return 0.0


class Trace:
    """All spans sharing one trace_id (= one round of one run)."""

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.spans: Dict[str, SpanNode] = {}

    def _node(self, span_id: str) -> SpanNode:
        sn = self.spans.get(span_id)
        if sn is None:
            sn = self.spans[span_id] = SpanNode(span_id)
        return sn

    def add(self, rec: Dict[str, Any]) -> None:
        topic = rec.get("topic")
        sn = self._node(str(rec.get("span_id")))
        if topic == "span_start":
            # duplicate starts (a re-delivered record) keep the FIRST copy:
            # ids are deterministic, so first-wins is order-stable
            if sn.start is None:
                sn.start = rec
        elif topic == "span_end":
            if sn.end is None:
                sn.end = rec
        else:
            sn.events.append(rec)

    def link(self) -> None:
        for sn in self.spans.values():
            sn.children = []
        for sn in self.spans.values():
            pid = sn.parent_span_id
            if pid is not None and pid in self.spans:
                self.spans[pid].children.append(sn)

    def roots(self) -> List[SpanNode]:
        return [sn for sn in self.spans.values()
                if sn.start is not None and sn.parent_span_id is None]

    def round_idx(self) -> Optional[int]:
        for sn in self.spans.values():
            ri = sn.round_idx
            if ri is not None:
                return ri
        return None

    def problems(self) -> List[str]:
        """Integrity violations: orphans, unclosed spans, ends that never
        started, zero-or-many roots."""
        out: List[str] = []
        roots = self.roots()
        if (self.round_idx() is None and roots
                and all(r.name in RUN_SPANS for r in roots)):
            pass  # the run's own trace: spans outside any round, each a root
        elif len(roots) != 1:
            out.append(f"{len(roots)} root spans (expected exactly 1: the round)")
        elif roots[0].name != "round":
            out.append(f"root span is {roots[0].name!r} (expected 'round')")
        for sn in sorted(self.spans.values(), key=lambda s: s.span_id):
            if sn.start is None and sn.end is not None:
                out.append(f"span {sn.span_id} ({sn.name}) ended without starting")
            if sn.start is not None and sn.end is None:
                out.append(f"span {sn.span_id} ({sn.name}, node={sn.node}) "
                           "never closed")
            pid = sn.parent_span_id
            if pid is not None and pid not in self.spans:
                out.append(f"span {sn.span_id} ({sn.name}) is an orphan "
                           f"(parent {pid} unknown)")
        return out

    def critical_path(self) -> List[SpanNode]:
        """Root-to-leaf chain following, at each level, the child that
        closed LAST — the spans the round's wall time actually waited on."""
        roots = self.roots()
        if not roots:
            return []
        self.link()
        path = [roots[0]]
        seen = {roots[0].span_id}
        while path[-1].children:
            nxt = max(path[-1].children, key=lambda s: (s.end_ts(), s.span_id))
            if nxt.span_id in seen:  # defensive: corrupt parent links
                break
            seen.add(nxt.span_id)
            path.append(nxt)
        return path

    def is_async(self) -> bool:
        """Buffered-async trace: the round span's ``mode`` says so, or a
        ``buffer.flush`` span is present (server-lifetime traces)."""
        for root in self.roots():
            if "async" in str((root.start or {}).get("mode", "")):
                return True
        return any(sn.name == "buffer.flush" for sn in self.spans.values())

    def flushes(self) -> List[SpanNode]:
        """``buffer.flush`` spans in close order (one per drained buffer)."""
        return sorted(
            (sn for sn in self.spans.values()
             if sn.name == "buffer.flush" and sn.start is not None),
            key=lambda s: (s.end_ts(), s.span_id))

    def _root_start_ts(self) -> float:
        roots = self.roots()
        if roots and isinstance((roots[0].start or {}).get("ts"), (int, float)):
            return float(roots[0].start["ts"])
        return 0.0

    def attribution(self) -> Optional[Dict[str, Any]]:
        """Where the round's wall time went: per-name SELF seconds (span
        duration minus its children's — concurrent children can legitimately
        sum past the round wall), plus the compile-vs-execute split the
        simulator attached to the round-end record when available."""
        roots = self.roots()
        if not roots:
            return None
        self.link()
        root = roots[0]
        by_name: Dict[str, float] = {}
        seen = set()

        def walk(sn: SpanNode) -> None:
            if sn.span_id in seen:  # defensive: corrupt parent links
                return
            seen.add(sn.span_id)
            child_sum = 0.0
            for c in sn.children:
                child_sum += c.duration_s()
                walk(c)
            self_s = max(0.0, sn.duration_s() - child_sum)
            by_name[sn.name] = by_name.get(sn.name, 0.0) + self_s

        walk(root)
        end = root.end or {}
        out: Dict[str, Any] = {
            "round": self.round_idx(),
            "round_s": round(root.duration_s(), 6),
            "n_spans": len(self.spans),
            "self_seconds": {
                k: round(v, 6)
                for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])},
        }
        for key in ("compile_s", "execute_s"):
            if isinstance(end.get(key), (int, float)):
                out[key] = float(end[key])
        return out

    def stragglers(self, slow_factor: float) -> List[Tuple[SpanNode, float, bool]]:
        """``client.train`` spans ranked slowest-first with their duration
        (sync) or time-to-report since cycle open (async) and a flag for
        > slow_factor x median."""
        trains = [sn for sn in self.spans.values()
                  if sn.name == "client.train" and sn.start is not None]
        if not trains:
            return []
        if self.is_async():
            t0 = self._root_start_ts()
            metric = lambda sn: max(0.0, sn.end_ts() - t0)  # noqa: E731
        else:
            metric = lambda sn: sn.duration_s()  # noqa: E731
        vals = sorted(metric(sn) for sn in trains)
        median = vals[len(vals) // 2]
        ranked = sorted(trains, key=lambda s: -metric(s))
        return [(sn, metric(sn),
                 median > 0 and metric(sn) > slow_factor * median)
                for sn in ranked]

    def clients(self) -> List[Dict[str, Any]]:
        """Per-participant compute/network/deferred attribution and the
        dominant-phase straggler class.  Participants are keyed by the
        ``client`` attr when present (sp simulation) else the emitting
        ``node`` (distributed ranks); network is the ``upload`` span's
        self-time (its duration minus nested children — the server-side
        receive work parents under the upload context); deferred is, in
        async traces, the report latency since cycle open that neither
        compute nor network explains (buffer residency)."""
        self.link()

        def key_of(sn: SpanNode) -> Any:
            st = sn.start or {}
            return st.get("client", st.get("node", "?"))

        per: Dict[Any, Dict[str, float]] = {}

        def slot(k: Any) -> Dict[str, float]:
            return per.setdefault(k, {"compute_s": 0.0, "network_s": 0.0,
                                      "deferred_s": 0.0, "_last_end": 0.0})

        for sn in self.spans.values():
            if sn.start is None:
                continue
            if sn.name == "client.train":
                d = slot(key_of(sn))
                d["compute_s"] += sn.duration_s()
            elif sn.name == "upload":
                d = slot(key_of(sn))
                child_s = sum(c.duration_s() for c in sn.children)
                d["network_s"] += max(0.0, sn.duration_s() - child_s)
            else:
                continue
            d["_last_end"] = max(d["_last_end"], sn.end_ts())
        t0 = self._root_start_ts()
        is_async = self.is_async()
        out: List[Dict[str, Any]] = []
        for k in sorted(per, key=str):
            d = per[k]
            if is_async and t0 > 0 and d["_last_end"] > 0:
                ttr = max(0.0, d["_last_end"] - t0)
                d["deferred_s"] = max(
                    0.0, ttr - d["compute_s"] - d["network_s"])
            del d["_last_end"]
            phases = {"compute": d["compute_s"], "network": d["network_s"],
                      "deferred": d["deferred_s"]}
            cls = max(phases, key=phases.get)  # ties: compute wins (order)
            out.append({"client": k,
                        "compute_s": round(d["compute_s"], 6),
                        "network_s": round(d["network_s"], 6),
                        "deferred_s": round(d["deferred_s"], 6),
                        "class": cls})
        return out


def build_traces(records: Iterable[Dict[str, Any]]) -> Dict[str, Trace]:
    traces: Dict[str, Trace] = {}
    for rec in records:
        tid = str(rec.get("trace_id"))
        tr = traces.get(tid)
        if tr is None:
            tr = traces[tid] = Trace(tid)
        tr.add(rec)
    for tr in traces.values():
        tr.link()
    return traces


def _fmt_path(path: List[SpanNode]) -> str:
    return " > ".join(
        f"{sn.name}[node={sn.node}, {sn.duration_s():.3f}s]" for sn in path
    )


def trace_payload(tr: Trace, slow_factor: float) -> Dict[str, Any]:
    """One trace as machine-readable data (the ``--format json`` shape —
    same numbers as the text report, so perf tooling and CI consume this
    instead of screen-scraping)."""
    problems = tr.problems()
    roots = tr.roots()
    metric_name = "time_to_report" if tr.is_async() else "dur"
    return {
        "trace_id": tr.trace_id,
        "round": tr.round_idx(),
        "duration_s": round(roots[0].duration_s(), 6) if roots else 0.0,
        "n_spans": len(tr.spans),
        "async": tr.is_async(),
        "critical_path": [
            {"name": sn.name, "node": sn.node,
             "duration_s": round(sn.duration_s(), 6)}
            for sn in tr.critical_path()],
        "stragglers": [
            {"node": sn.node, "metric": metric_name,
             "value": round(d, 6), "slow": bool(slow)}
            for sn, d, slow in tr.stragglers(slow_factor)],
        "flushes": [
            {"round": fl.round_idx,
             "n_deltas": (fl.start or {}).get("n_deltas"),
             "capacity": (fl.start or {}).get("capacity"),
             "reason": (fl.start or {}).get("reason"),
             "duration_s": round(fl.duration_s(), 6)}
            for fl in tr.flushes()],
        "events": [
            {k: v for k, v in sorted(ev.items())
             if k not in ("topic", "trace_id", "span_id")}
            for sn in tr.spans.values() for ev in sn.events],
        "attribution": tr.attribution(),
        "clients": tr.clients(),
        "problems": problems,
    }


def _ordered(traces: Dict[str, Trace]) -> List[Trace]:
    return sorted(
        traces.values(),
        key=lambda t: (t.round_idx() if t.round_idx() is not None else -1,
                       t.trace_id),
    )


def report_json(traces: Dict[str, Trace], slow_factor: float,
                round_filter: Optional[int] = None, out=None) -> int:
    """Emit the whole report as one JSON document; returns problem count."""
    out = out if out is not None else sys.stdout
    payloads = [trace_payload(tr, slow_factor) for tr in _ordered(traces)
                if round_filter is None or tr.round_idx() == round_filter]
    n_problems = sum(len(p["problems"]) for p in payloads)
    json.dump({"n_traces": len(payloads), "n_problems": n_problems,
               "traces": payloads}, out, sort_keys=True)
    out.write("\n")
    return n_problems


def phase_profile(traces: Dict[str, Trace]) -> Dict[str, float]:
    """Mean per-round self-seconds by span name (phases absent in a round
    count as zero, so the means are comparable across runs with different
    round counts)."""
    samples: Dict[str, float] = {}
    n = 0
    for tr in _ordered(traces):
        att = tr.attribution()
        if not att:
            continue
        n += 1
        for name, secs in att["self_seconds"].items():
            samples[name] = samples.get(name, 0.0) + float(secs)
    if n == 0:
        return {}
    return {k: v / n for k, v in samples.items()}


def _round_seconds(traces: Dict[str, Trace]) -> float:
    durs = sorted(
        tr.roots()[0].duration_s() for tr in traces.values() if tr.roots())
    return durs[len(durs) // 2] if durs else 0.0


def diff_report(path_a: str, path_b: str, tolerance: float,
                out=None) -> int:
    """Compare run B against baseline run A: median round wall time and
    mean per-phase self-seconds.  Returns the number of REGRESSED phases
    (mean self-time grew by more than ``tolerance`` fractionally AND by an
    absolute floor that ignores sub-millisecond jitter)."""
    out = out if out is not None else sys.stdout
    ta = build_traces(load_records(path_a))
    tb = build_traces(load_records(path_b))
    prof_a, prof_b = phase_profile(ta), phase_profile(tb)
    ra, rb = _round_seconds(ta), _round_seconds(tb)
    print(f"diff: A={path_a} ({len(ta)} traces)  "
          f"B={path_b} ({len(tb)} traces)", file=out)
    print(f"  round median: A={ra:.3f}s  B={rb:.3f}s  "
          f"delta={rb - ra:+.3f}s", file=out)
    regressed = 0
    for name in sorted(set(prof_a) | set(prof_b)):
        a, b = prof_a.get(name, 0.0), prof_b.get(name, 0.0)
        flag = ""
        if b > a * (1.0 + tolerance) and b - a > 1e-3:
            flag = "  << REGRESSED"
            regressed += 1
        pct = (100.0 * (b - a) / a) if a > 0 else float("inf") if b > 0 else 0.0
        print(f"  {name:<20s} A={a:8.4f}s  B={b:8.4f}s  "
              f"{pct:+7.1f}%{flag}", file=out)
    if regressed:
        print(f"trace_report: {regressed} regressed phase(s)", file=out)
    return regressed


def report(traces: Dict[str, Trace], slow_factor: float,
           round_filter: Optional[int] = None, out=None,
           attribution: bool = False, clients: bool = False) -> int:
    """Print the per-round report; returns the total problem count."""
    # bind the stream late: a def-time sys.stdout default would dodge any
    # redirection installed after import (test capture, CLI piping)
    out = out if out is not None else sys.stdout
    n_problems = 0
    for tr in _ordered(traces):
        ri = tr.round_idx()
        if round_filter is not None and ri != round_filter:
            continue
        problems = tr.problems()
        n_problems += len(problems)
        roots = tr.roots()
        dur = roots[0].duration_s() if roots else 0.0
        print(f"trace {tr.trace_id}  round={ri}  spans={len(tr.spans)}  "
              f"duration={dur:.3f}s", file=out)
        path = tr.critical_path()
        if path:
            print(f"  critical path: {_fmt_path(path)}", file=out)
        is_async = tr.is_async()
        for fl in tr.flushes():
            st = fl.start or {}
            n = st.get("n_deltas", "?")
            cap = st.get("capacity", None)
            occ = (f"{int(n) / int(cap):.2f}"
                   if isinstance(n, int) and isinstance(cap, int) and cap
                   else "?")
            stal = "/".join(
                str(st.get(k, "?")) for k in
                ("staleness_min", "staleness_mean", "staleness_max"))
            print(f"  flush round={fl.round_idx} n_deltas={n} "
                  f"capacity={cap} occupancy={occ} "
                  f"reason={st.get('reason', '?')} "
                  f"staleness(min/mean/max)={stal} "
                  f"dur={fl.duration_s():.3f}s", file=out)
        if attribution:
            att = tr.attribution()
            if att:
                split = ""
                if "compile_s" in att:
                    split = (f"  compile={att['compile_s']:.3f}s "
                             f"execute={att.get('execute_s', 0.0):.3f}s")
                print(f"  attribution: round={att['round_s']:.3f}s"
                      f"{split}", file=out)
                for name, secs in att["self_seconds"].items():
                    if secs <= 0.0:
                        continue
                    pct = (100.0 * secs / att["round_s"]
                           if att["round_s"] > 0 else 0.0)
                    print(f"    {name:<20s} {secs:8.3f}s  {pct:5.1f}%",
                          file=out)
        if clients:
            rows = tr.clients()
            if rows:
                print("  client     compute_s  network_s  deferred_s  class",
                      file=out)
                for row in rows:
                    print(f"  {str(row['client']):<9s}"
                          f"  {row['compute_s']:9.4f}"
                          f"  {row['network_s']:9.4f}"
                          f"  {row['deferred_s']:10.4f}"
                          f"  {row['class']}", file=out)
        metric_name = "time_to_report" if is_async else "dur"
        for sn, d, slow in tr.stragglers(slow_factor):
            flag = "  << STRAGGLER" if slow else ""
            print(f"  client.train node={sn.node}: "
                  f"{metric_name}={d:.3f}s{flag}", file=out)
        events = [ev for sn in tr.spans.values() for ev in sn.events]
        for ev in events:
            print(f"  event {ev.get('event')}: node={ev.get('node')} "
                  + " ".join(f"{k}={v}" for k, v in sorted(ev.items())
                             if k not in ("topic", "trace_id", "span_id",
                                          "event", "node", "ts")),
                  file=out)
        for p in problems:
            print(f"  PROBLEM: {p}", file=out)
    return n_problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="mlops JSONL file(s)")
    ap.add_argument("--round", type=int, default=None,
                    help="report only this round index")
    ap.add_argument("--slow-factor", type=float, default=2.0,
                    help="straggler flag threshold vs round median (default 2.0)")
    ap.add_argument("--assert-closed", action="store_true",
                    help="exit 2 if any trace has orphan/unclosed spans")
    ap.add_argument("--attribution", action="store_true",
                    help="per-round wall-clock attribution: self-time by "
                         "span name + the simulator's compile/execute split")
    ap.add_argument("--clients", action="store_true",
                    help="per-participant compute/network/deferred table "
                         "with the dominant-phase straggler class")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"), default=None,
                    help="compare run B against baseline run A: median "
                         "round time and mean per-phase self-seconds; "
                         "exit 1 when any phase regressed")
    ap.add_argument("--diff-tolerance", type=float, default=0.25,
                    help="fractional growth in a phase's mean self-time "
                         "counted as a regression (default 0.25)")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="json emits one machine-readable document with the "
                         "same data as the text report")
    args = ap.parse_args(argv)
    if args.diff is not None:
        return 1 if diff_report(args.diff[0], args.diff[1],
                                args.diff_tolerance) else 0
    if not args.paths:
        ap.error("at least one JSONL path is required (or use --diff A B)")

    records: List[Dict[str, Any]] = []
    for path in args.paths:
        records.extend(load_records(path))
    if not records:
        if args.format == "json":
            print(json.dumps({"n_traces": 0, "n_problems": 0, "traces": []}))
        else:
            print("trace_report: no span records found", flush=True)
        return 0
    traces = build_traces(records)
    if args.format == "json":
        n_problems = report_json(traces, args.slow_factor, args.round)
        return 2 if n_problems and args.assert_closed else 0
    n_problems = report(traces, args.slow_factor, args.round,
                        attribution=args.attribution, clients=args.clients)
    if n_problems:
        print(f"trace_report: {n_problems} integrity problem(s)", flush=True)
        if args.assert_closed:
            return 2
    else:
        print(f"trace_report: {len(traces)} trace(s), all closed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
