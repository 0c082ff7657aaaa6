#!/usr/bin/env python
"""Anti-flake gate for the chaos suite.

Runs the fast chaos matrix plus the server-kill/restart tests
(``tests/test_fault_tolerance.py``), the trace-integrity chaos tests
(``tests/test_obs.py`` — every completed round must reconstruct as one
closed span tree even under drop/dup/delay/server_kill) AND the
compiled-aggregation chaos tests (``tests/test_agg_plane.py`` —
retransmit/dup chaos with ``agg_plane=compiled`` must converge
bit-identical to the fault-free host run) AND the buffered-async chaos
tests (``tests/test_async_fl.py`` — drop/dup/delay plus ``server_kill``
mid-buffer must converge deterministically with exactly-once delta
accounting) AND the staged-ingest chaos tests (``tests/test_ingest.py`` —
the full chaos plan and the server kill with ``ingest_pipeline=True`` and
group commit must converge bit-identical to the host-path model, with
every traced round still one closed span tree) AND the telemetry-plane
chaos tests (``tests/test_telemetry.py`` — drop/dup/delay/server_kill
with ``obs_telemetry=1`` must converge bit-identical to the
telemetry-off run, with the remote spans grafted and the seq gap/dup
accounting exact) AND the sharded-server-state chaos leg
(``tests/test_fault_tolerance.py -k sharded_state`` — a server kill
AFTER the first FedOpt round with ``server_state=sharded`` must restore
the model-sharded optimizer state bit-identically) AND the elastic leg
(``tests/test_fault_tolerance.py -k elastic`` plus the
``TestElasticRemesh`` suite in ``tests/test_agg_plane.py`` — a
``mesh_shrink`` topology fault mid-round, and a server kill restarted
with the model axis shrunk 4→2, must both re-shard through the portable
state codec and converge bit-identical to the fixed-mesh run with
exactly-once accounting) AND the defense leg
(``tests/test_security_plane.py -k secagg_dropout`` — a SecAgg round
with a client dropped mid-upload plus a server kill mid-round must
unmask BIT-IDENTICALLY to the uninterrupted round, with exactly-once
duplicate accounting, and abort below the reconstruction threshold)
AND the hierarchy leg (``tests/test_hierarchy.py -k hierarchy`` — 2- and
3-level edge-aggregator trees under the full drop/dup/delay/reset chaos
plan, plus an edge kill mid-round, must close the round BIT-IDENTICALLY
to the flat topology with exactly-once forward accounting at the root)
AND the chunked-upload leg (``tests/test_chunking.py -k chunk`` — the
full drop/dup/delay/reset/torn-frame/``mid_message_disconnect`` plan
over the ``comm_chunk`` vocabulary plus a server kill BETWEEN chunks of
live streams must converge BIT-IDENTICALLY to the whole-message run,
resuming interrupted uploads from the last acked chunk with exactly-once
replay accounting) AND the health leg (``tests/test_health.py -k health``
— an injected ingest-queue stall, a killed chunk-pump thread, and a
silent edge aggregator must each fire the RIGHT detector at its exact
deadline on the injected clock with EXACTLY ONE flight dump per
incident, and a fault-free run with ``obs_health=1`` must converge
bit-identical to the plane-off run with every round's span tree closed)
N consecutive times in
fresh interpreter processes and fails on the FIRST non-green run.
A fault-injection suite that only mostly passes is worse than none —
operators stop believing red — so new fault kinds / backends must hold up
under this before they land unmarked.

Before the pytest loop it runs the **perf gate** over the checked-in
bench trajectory, advisory-then-strict: first ``tools/perf_gate.py
--advisory`` for the full report, then strict — any dark round or
regression fails the chaos gate before a single pytest process spawns.
A checkout without ``BENCH_r*.json`` artifacts (this one, today) skips the
leg; ``--skip-perf-gate`` opts out explicitly.

It also runs the **fedlint leg** (``tools/fedlint.py``) the same way:
advisory first (the full report prints, including pragma/baseline
accounting, so suppressions stay visible), then strict — any finding
from the race / ack-ordering / purity analyzers or the four ported lint
contracts fails the gate before a single pytest process spawns.
``--skip-fedlint`` opts out.

Usage::

    python tools/chaos_check.py --runs 5
    python tools/chaos_check.py --runs 3 -k "chaos_matrix"
    python tools/chaos_check.py --runs 3 -k "server_kill"
    python tools/chaos_check.py --runs 3 -k "trace_integrity"
    python tools/chaos_check.py --runs 3 -k "agg_plane"
    python tools/chaos_check.py --runs 3 -k "async_fl"
    python tools/chaos_check.py --runs 3 -k "ingest"
    python tools/chaos_check.py --runs 3 -k "telemetry"
    python tools/chaos_check.py --runs 3 -k "sharded_state"
    python tools/chaos_check.py --runs 3 -k "elastic or mesh_shrink"
    python tools/chaos_check.py --runs 3 -k "secagg_dropout"
    python tools/chaos_check.py --runs 3 -k "hierarchy"
    python tools/chaos_check.py --runs 3 -k "chunk"
    python tools/chaos_check.py --runs 3 -k "health"
    python tools/chaos_check.py --runs 3 --skip-perf-gate
    python tools/chaos_check.py --runs 3 --skip-fedlint
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def run_perf_gate(timeout: float) -> int:
    """Advisory pass over the trajectory (full report), then strict.
    Returns the strict leg's rc."""
    import glob
    if not glob.glob(os.path.join(REPO_ROOT, "BENCH_r*.json")):
        print("chaos_check: perf gate skipped — no BENCH_r*.json "
              "trajectory in this checkout", flush=True)
        return 0
    gate = [sys.executable, os.path.join(REPO_ROOT, "tools", "perf_gate.py")]
    try:
        print("chaos_check: perf gate (advisory, full trajectory)",
              flush=True)
        subprocess.run(gate + ["--advisory"], cwd=REPO_ROOT, timeout=timeout)
        print("chaos_check: perf gate (strict)", flush=True)
        strict = subprocess.run(gate, cwd=REPO_ROOT, timeout=timeout)
    except subprocess.TimeoutExpired:
        print("chaos_check: perf gate TIMED OUT", flush=True)
        return 2
    return strict.returncode


def run_fedlint(timeout: float) -> int:
    """Advisory pass (full report, suppressions visible), then strict.
    Returns the strict leg's rc — mirrors run_perf_gate."""
    fedlint = [sys.executable, os.path.join(REPO_ROOT, "tools", "fedlint.py")]
    try:
        print("chaos_check: fedlint (advisory, full report)", flush=True)
        subprocess.run(fedlint + ["--advisory"], cwd=REPO_ROOT,
                       timeout=timeout)
        print("chaos_check: fedlint (strict)", flush=True)
        strict = subprocess.run(fedlint, cwd=REPO_ROOT, timeout=timeout)
    except subprocess.TimeoutExpired:
        print("chaos_check: fedlint TIMED OUT", flush=True)
        return 2
    return strict.returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", "-n", type=int, default=3,
                    help="consecutive green runs required (default 3)")
    ap.add_argument(
        "-k", dest="keyword",
        default="chaos or server_kill or trace_integrity or agg_plane "
                "or async_fl or ingest or telemetry or sharded_state "
                "or elastic or mesh_shrink or secagg_dropout or hierarchy "
                "or chunk or health",
        help='pytest -k selector (default: "chaos or server_kill or '
             'trace_integrity or agg_plane or async_fl or ingest or '
             'telemetry or sharded_state or elastic or mesh_shrink or '
             'secagg_dropout or hierarchy or chunk or health")')
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-run wall-clock bound in seconds")
    ap.add_argument("--skip-perf-gate", action="store_true",
                    help="skip the bench-trajectory perf gate leg")
    ap.add_argument("--skip-fedlint", action="store_true",
                    help="skip the static-analysis (fedlint) leg")
    args = ap.parse_args(argv)

    if not args.skip_perf_gate:
        gate_rc = run_perf_gate(args.timeout)
        if gate_rc != 0:
            print(f"chaos_check: PERF GATE FAILED (rc={gate_rc}) — a new "
                  "dark round or regression in the bench trajectory",
                  flush=True)
            return 1

    if not args.skip_fedlint:
        lint_rc = run_fedlint(args.timeout)
        if lint_rc != 0:
            print(f"chaos_check: FEDLINT FAILED (rc={lint_rc}) — fix the "
                  "finding or carry a justified pragma "
                  "(docs/STATIC_ANALYSIS.md)", flush=True)
            return 1

    env = dict(os.environ, JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"))
    cmd = [sys.executable, "-m", "pytest", "tests/test_fault_tolerance.py",
           "tests/test_obs.py", "tests/test_agg_plane.py",
           "tests/test_async_fl.py", "tests/test_ingest.py",
           "tests/test_telemetry.py", "tests/test_security_plane.py",
           "tests/test_hierarchy.py", "tests/test_chunking.py",
           "tests/test_health.py",
           "-q", "-k", args.keyword, "-p", "no:cacheprovider"]
    for i in range(1, args.runs + 1):
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, cwd=REPO_ROOT, env=env,
                                  timeout=args.timeout)
        except subprocess.TimeoutExpired:
            print(f"chaos_check: run {i}/{args.runs} TIMED OUT "
                  f"after {args.timeout:.0f}s", flush=True)
            return 2
        if proc.returncode != 0:
            print(f"chaos_check: FLAKE — run {i}/{args.runs} exited "
                  f"{proc.returncode} after {time.time() - t0:.1f}s", flush=True)
            return 1
        print(f"chaos_check: run {i}/{args.runs} green "
              f"({time.time() - t0:.1f}s)", flush=True)
    print(f"chaos_check: {args.runs} consecutive green runs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
