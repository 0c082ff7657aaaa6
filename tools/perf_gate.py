#!/usr/bin/env python
"""Perf-regression gate over a bench trajectory.

A bench round that goes dark (nonzero rc, no metric line) or regresses
should fail a check, not wait for a human to read a JSON tail.  The gate
parses ``BENCH_rNN.json`` driver records (``{"n", "cmd", "rc", "tail"}``
with the bench's single metric JSON line embedded in ``tail``) plus
``BASELINE.json`` and fails on:

* **dark rounds** — nonzero rc or no parseable metric line.
* **schema violations** — bench.py stamps ``bench_schema`` / ``mode`` /
  ``degraded_reason`` / ``git_rev`` (schema 2); a schema-stamped record
  missing its required keys fails, as does a legacy record without
  ``metric``/numeric ``value``.
* **regressions** — for each relative key (``vs_baseline``,
  ``agg_speedup``, ``uploads_per_s``, ``async_flushes_per_s``,
  ``async_deltas_per_s``, ``telemetry_rounds_per_s``,
  ``fanin_uploads_per_s_flat`` / ``fanin_uploads_per_s_edge``) the LATEST value
  must stay within ``--tolerance`` of the median of the prior rounds
  that report the key (keys absent in older-schema rounds are simply
  not banded yet).  ``obs_overhead_frac`` and ``telemetry_overhead_frac``
  are lower-better and capped absolutely by ``--obs-overhead-max``.
  ``resize_downtime_s`` / ``remesh_recompile_s`` (elastic resize) are
  lower-better and banded RELATIVELY: the latest value must stay under
  ``(1 + tolerance) x`` the prior-round median.
  ``BASELINE.json``'s ``published`` map, when populated, bands the same
  way against the published numbers.

``--advisory`` prints every violation but exits 0.  The tree holds no bench
trajectory at present (the pre-PR-1 records were taken through a retired
set-up and are gone; PERF_LEDGER.jsonl is the driver's record now), so with
no paths given the gate reports "no bench files found" and exits 2.

Usage::

    python tools/perf_gate.py                       # BENCH_r*.json + BASELINE.json
    python tools/perf_gate.py BENCH_r01.json BENCH_r02.json
    python tools/perf_gate.py --advisory --format json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# must match bench.BENCH_SCHEMA (pinned by tests/test_perf_gate.py so the
# two can't drift); the gate itself stays importable without jax
BENCH_SCHEMA_CURRENT = 2

# higher-is-better relative keys banded against the prior-round median
RELATIVE_KEYS = ("vs_baseline", "agg_speedup", "round_update_speedup",
                 "broadcast_shrink", "uploads_per_s",
                 "uploads_per_s_host", "uploads_per_s_pipelined",
                 "async_flushes_per_s", "async_deltas_per_s",
                 "telemetry_rounds_per_s", "defended_round_speedup",
                 "fanin_uploads_per_s_flat", "fanin_uploads_per_s_edge",
                 "chunked_goodput_frac_lossy",
                 "rounds_per_s", "clients_simulated_per_s")
# lower-is-better: absolute cap (observability must stay cheap — spans,
# registry, exposition, and now the telemetry plane all share the budget)
OVERHEAD_KEYS = ("obs_overhead_frac", "telemetry_overhead_frac",
                 "dp_overhead_frac", "chunk_overhead_frac",
                 "health_overhead_frac")
# per-key overrides of --obs-overhead-max: the DP stage pays real compute
# (per-client clip + counter-based noise over the whole update matrix), so
# against the small synthetic bench round its frac is a few x, not a few %.
# The wide cap is a runaway backstop (a recompile-per-round or accidentally
# quadratic stage); creep is caught by the trajectory band below.
# Chunk framing is pure wire bookkeeping — at the bench's representative
# 64 KiB chunks the headers must stay under 5% of the payload or the
# resumability win is being eaten by the framing itself.
OVERHEAD_BUDGETS = {"dp_overhead_frac": 25.0, "chunk_overhead_frac": 0.05}
# lower-is-better relative keys banded against the prior-round median
# (elastic resize: downtime of an in-place remesh and its recompile slice
# must not creep — a topology change should stay a sub-round blip; same
# contract for the SecAgg mask/unmask cycle and the DP stage's relative
# cost)
LATENCY_KEYS = ("resize_downtime_s", "remesh_recompile_s",
                "secagg_mask_s", "dp_overhead_frac")

_MODES = ("full", "failed")


def extract_metric_line(tail: str) -> Optional[Dict[str, Any]]:
    """The LAST line of ``tail`` that parses to a dict with a ``metric``
    key — the bench contract is exactly one such line on stdout."""
    found = None
    for line in str(tail or "").splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            found = obj
    return found


def load_round(path: str, position: int) -> Dict[str, Any]:
    """One normalized trajectory entry: ``{"path", "round", "rc",
    "parsed"}``.  Accepts the driver wrapper format or a bare metric
    record (synthetic gate inputs)."""
    with open(path, "r", encoding="utf-8") as f:
        obj = json.load(f)
    if not isinstance(obj, dict):
        raise ValueError(f"{path}: expected a JSON object")
    if "tail" in obj or "rc" in obj:
        return {
            "path": path,
            "round": int(obj.get("n", position)),
            "rc": int(obj.get("rc", 0)),
            "parsed": extract_metric_line(obj.get("tail", "")),
        }
    # bare metric record
    return {"path": path, "round": int(obj.get("round", position)),
            "rc": 0, "parsed": obj if "metric" in obj else None}


def validate_record(entry: Dict[str, Any]) -> List[str]:
    """Schema-contract violations for one light round's parsed record."""
    rec = entry["parsed"]
    out: List[str] = []
    where = f"round {entry['round']} ({os.path.basename(entry['path'])})"
    schema = rec.get("bench_schema")
    if schema is None:
        # legacy (pre-schema) record: minimum viable contract
        if not isinstance(rec.get("value"), (int, float)):
            out.append(f"{where}: legacy record has non-numeric value "
                       f"{rec.get('value')!r}")
        return out
    if not isinstance(schema, int) or not 1 <= schema <= BENCH_SCHEMA_CURRENT:
        out.append(f"{where}: unknown bench_schema {schema!r} "
                   f"(gate understands <= {BENCH_SCHEMA_CURRENT})")
        return out
    mode = rec.get("mode")
    if mode not in _MODES:
        out.append(f"{where}: mode must be one of {_MODES}, got {mode!r}")
    if mode == "failed" and not rec.get("degraded_reason"):
        out.append(f"{where}: failed record missing degraded_reason")
    if mode == "full" and rec.get("degraded_reason") not in (None, ""):
        out.append(f"{where}: full record carries degraded_reason "
                   f"{rec.get('degraded_reason')!r}")
    if "git_rev" not in rec:
        out.append(f"{where}: schema-{schema} record missing git_rev")
    if mode != "failed" and not isinstance(rec.get("value"), (int, float)):
        out.append(f"{where}: non-numeric value {rec.get('value')!r}")
    return out


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def check_trajectory(entries: List[Dict[str, Any]], tolerance: float,
                     obs_overhead_max: float,
                     baseline: Optional[Dict[str, Any]] = None,
                     ) -> List[str]:
    """Every violation in the trajectory (empty = gate passes)."""
    violations: List[str] = []
    light: List[Dict[str, Any]] = []
    for entry in entries:
        dark = entry["rc"] != 0 or entry["parsed"] is None
        if dark:
            why = (f"rc={entry['rc']}" if entry["rc"] != 0
                   else "no parseable metric line in tail")
            violations.append(
                f"round {entry['round']} "
                f"({os.path.basename(entry['path'])}): DARK ROUND — {why}")
            continue
        violations.extend(validate_record(entry))
        light.append(entry)

    # tolerance bands: latest vs median of the prior rounds carrying the key
    for key in RELATIVE_KEYS:
        series = [(e["round"], float(e["parsed"][key])) for e in light
                  if isinstance(e["parsed"].get(key), (int, float))]
        if len(series) < 2:
            continue
        *prior, (rnd, latest) = series
        med = _median([v for _, v in prior])
        floor = (1.0 - tolerance) * med
        if latest < floor:
            violations.append(
                f"round {rnd}: REGRESSION — {key}={latest:g} fell below "
                f"{floor:g} ({(1.0 - tolerance):.0%} of prior median "
                f"{med:g})")
    # lower-is-better bands: latest must stay under the mirrored ceiling
    for key in LATENCY_KEYS:
        series = [(e["round"], float(e["parsed"][key])) for e in light
                  if isinstance(e["parsed"].get(key), (int, float))]
        if len(series) < 2:
            continue
        *prior, (rnd, latest) = series
        med = _median([v for _, v in prior])
        ceiling = (1.0 + tolerance) * med
        if latest > ceiling:
            violations.append(
                f"round {rnd}: REGRESSION — {key}={latest:g} rose above "
                f"{ceiling:g} ({(1.0 + tolerance):.0%} of prior median "
                f"{med:g})")
    for e in light:
        for key in OVERHEAD_KEYS:
            frac = e["parsed"].get(key)
            cap = OVERHEAD_BUDGETS.get(key, obs_overhead_max)
            if isinstance(frac, (int, float)) and frac > cap:
                violations.append(
                    f"round {e['round']}: OBS OVERHEAD — {key}="
                    f"{frac:g} exceeds the {cap:g} budget")

    published = (baseline or {}).get("published") or {}
    if light and isinstance(published, dict):
        latest = light[-1]["parsed"]
        for key, ref in published.items():
            got = latest.get(key)
            if (isinstance(ref, (int, float))
                    and isinstance(got, (int, float))
                    and got < (1.0 - tolerance) * float(ref)):
                violations.append(
                    f"round {light[-1]['round']}: REGRESSION vs published "
                    f"baseline — {key}={got:g} < {(1.0 - tolerance):.0%} "
                    f"of {ref:g}")
    return violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="BENCH round files in trajectory order "
                         "(default: BENCH_r*.json in the repo root)")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO_ROOT, "BASELINE.json"),
                    help="baseline metadata file (published reference keys)")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed fractional drop of a relative key vs the "
                         "prior-round median (default 0.5)")
    ap.add_argument("--obs-overhead-max", type=float, default=0.25,
                    help="absolute cap on obs_overhead_frac (default 0.25)")
    ap.add_argument("--advisory", action="store_true",
                    help="report violations but exit 0")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    paths = args.paths or sorted(
        glob.glob(os.path.join(REPO_ROOT, "BENCH_r*.json")))
    if not paths:
        print("perf_gate: no bench files found", flush=True)
        return 2
    try:
        entries = [load_round(p, i + 1) for i, p in enumerate(paths)]
    except (OSError, ValueError) as e:
        print(f"perf_gate: unreadable trajectory: {e}", flush=True)
        return 2
    baseline = None
    try:
        with open(args.baseline, "r", encoding="utf-8") as f:
            baseline = json.load(f)
    except (OSError, ValueError):
        pass  # baseline metadata is optional context, not a gate input

    violations = check_trajectory(
        entries, args.tolerance, args.obs_overhead_max, baseline=baseline)
    failed = bool(violations) and not args.advisory
    if args.format == "json":
        print(json.dumps({
            "ok": not violations,
            "advisory": bool(args.advisory),
            "n_rounds": len(entries),
            "violations": violations,
            "rounds": [{"round": e["round"], "rc": e["rc"],
                        "path": os.path.basename(e["path"]),
                        "dark": e["rc"] != 0 or e["parsed"] is None,
                        "mode": (e["parsed"] or {}).get("mode"),
                        "metric": (e["parsed"] or {}).get("metric"),
                        "value": (e["parsed"] or {}).get("value")}
                       for e in entries],
        }, sort_keys=True))
    else:
        for v in violations:
            print(f"perf_gate: {v}", flush=True)
        if violations:
            mode = "ADVISORY" if args.advisory else "FAIL"
            print(f"perf_gate: {mode} — {len(violations)} violation(s) "
                  f"across {len(entries)} round(s)", flush=True)
        else:
            print(f"perf_gate: OK — {len(entries)} round(s), no dark "
                  "rounds, no regressions", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
