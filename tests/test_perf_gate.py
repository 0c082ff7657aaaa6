"""tools/perf_gate.py — the perf-regression gate over BENCH trajectories.

These tests pin the acceptance contract on synthetic driver records: light
rounds pass, a dark round (nonzero rc / no metric line) FAILS the gate, a
regressed record fails the tolerance band, and the schema constants in
bench.py and perf_gate.py cannot drift apart.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import perf_gate


def _round_file(tmp_path, n, rec, rc=0):
    """One driver-format BENCH_rNN.json with ``rec`` as the metric line."""
    tail = "noise line\n" + (json.dumps(rec) + "\n" if rec is not None else "")
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps(
        {"n": n, "cmd": "python bench.py", "rc": rc, "tail": tail}))
    return str(path)


def _full(n, value, **extra):
    rec = {"metric": "m", "unit": "u", "value": value, "vs_baseline": value,
           "bench_schema": perf_gate.BENCH_SCHEMA_CURRENT, "mode": "full",
           "git_rev": "abc1234"}
    rec.update(extra)
    return rec


_LEGACY = {"metric": "m", "unit": "u", "value": 11000.0, "vs_baseline": 5.7}


class TestDarkRounds:
    """A trajectory in the driver's wrapper format: two light legacy
    (pre-schema) rounds, then rounds that died without a metric line."""

    def _paths(self, tmp_path, n_dark=1):
        paths = [_round_file(tmp_path, 1, _LEGACY),
                 _round_file(tmp_path, 2, dict(_LEGACY, value=10900.0))]
        paths += [_round_file(tmp_path, 3 + i, None, rc=1)
                  for i in range(n_dark)]
        return paths + ["--baseline", str(tmp_path / "nope")]

    def test_light_rounds_pass(self, tmp_path, capsys):
        assert perf_gate.main(self._paths(tmp_path, n_dark=0)) == 0
        assert "OK" in capsys.readouterr().out

    def test_dark_round_fails(self, tmp_path, capsys):
        assert perf_gate.main(self._paths(tmp_path)) == 1
        assert "DARK ROUND" in capsys.readouterr().out

    def test_every_dark_round_is_reported(self, tmp_path, capsys):
        assert perf_gate.main(self._paths(tmp_path, n_dark=3)) == 1
        assert capsys.readouterr().out.count("DARK ROUND") == 3

    def test_advisory_reports_but_exits_zero(self, tmp_path, capsys):
        assert perf_gate.main(self._paths(tmp_path) + ["--advisory"]) == 0
        out = capsys.readouterr().out
        assert "ADVISORY" in out and "DARK ROUND" in out

    def test_no_trajectory_in_the_tree_exits_2(self, capsys):
        # the pre-PR-1 BENCH_r0x.json records are gone; nothing to gate
        assert perf_gate.main([]) == 2
        assert "no bench files found" in capsys.readouterr().out


class TestTolerance:
    def test_regressed_latest_fails(self, tmp_path, capsys):
        paths = [_round_file(tmp_path, 1, _full(1, 10.0)),
                 _round_file(tmp_path, 2, _full(2, 11.0)),
                 _round_file(tmp_path, 3, _full(3, 2.0))]  # < 50% of median
        rc = perf_gate.main(paths + ["--baseline", str(tmp_path / "nope")])
        assert rc == 1
        assert "REGRESSION" in capsys.readouterr().out

    def test_within_band_passes(self, tmp_path):
        paths = [_round_file(tmp_path, 1, _full(1, 10.0)),
                 _round_file(tmp_path, 2, _full(2, 11.0)),
                 _round_file(tmp_path, 3, _full(3, 6.0))]  # >= 50% of median
        assert perf_gate.main(
            paths + ["--baseline", str(tmp_path / "nope")]) == 0

    def test_dark_round_between_light_ones_fails(self, tmp_path):
        paths = [_round_file(tmp_path, 1, _full(1, 10.0)),
                 _round_file(tmp_path, 2, None, rc=1),
                 _round_file(tmp_path, 3, _full(3, 10.0))]
        rc = perf_gate.main(paths + ["--baseline", str(tmp_path / "nope")])
        assert rc == 1

    def test_obs_overhead_cap(self, tmp_path, capsys):
        paths = [_round_file(tmp_path, 1,
                             _full(1, 10.0, obs_overhead_frac=0.4))]
        rc = perf_gate.main(paths + ["--baseline", str(tmp_path / "nope")])
        assert rc == 1
        assert "OBS OVERHEAD" in capsys.readouterr().out

    def test_published_baseline_bands_latest(self, tmp_path, capsys):
        base = tmp_path / "BASELINE.json"
        base.write_text(json.dumps({"published": {"vs_baseline": 10.0}}))
        paths = [_round_file(tmp_path, 1, _full(1, 3.0))]
        rc = perf_gate.main(paths + ["--baseline", str(base)])
        assert rc == 1
        assert "published" in capsys.readouterr().out


class TestSchemaValidation:
    def _gate(self, tmp_path, rec):
        path = _round_file(tmp_path, 1, rec)
        return perf_gate.main([path, "--baseline", str(tmp_path / "nope")])

    def test_failed_without_reason_fails(self, tmp_path):
        rec = _full(1, None)
        rec["mode"] = "failed"
        assert self._gate(tmp_path, rec) == 1

    def test_retired_degraded_mode_is_rejected(self, tmp_path):
        # bench.py has no CPU fallback any more: a record claiming one is
        # not a valid round
        rec = _full(1, 1.0, degraded_reason="no accelerator (cpu backend)")
        rec["mode"] = "degraded"
        assert self._gate(tmp_path, rec) == 1

    def test_full_with_reason_fails(self, tmp_path):
        assert self._gate(tmp_path, _full(1, 1.0, degraded_reason="x")) == 1

    def test_missing_git_rev_fails(self, tmp_path):
        rec = _full(1, 1.0)
        del rec["git_rev"]
        assert self._gate(tmp_path, rec) == 1

    def test_unknown_schema_fails(self, tmp_path):
        assert self._gate(
            tmp_path, _full(1, 1.0, bench_schema=99)) == 1

    def test_failed_mode_allows_null_value_but_needs_reason(self, tmp_path):
        rec = _full(1, None, degraded_reason="unhandled RuntimeError")
        rec["mode"] = "failed"
        assert self._gate(tmp_path, rec) == 0

    def test_legacy_record_numeric_value_passes(self, tmp_path):
        # pre-schema records stay valid
        assert self._gate(
            tmp_path, {"metric": "m", "unit": "u", "value": 3.0}) == 0

    def test_legacy_record_non_numeric_value_fails(self, tmp_path):
        assert self._gate(
            tmp_path, {"metric": "m", "unit": "u", "value": "fast"}) == 1


class TestOutputAndParsing:
    def test_json_format_payload(self, tmp_path, capsys):
        paths = [_round_file(tmp_path, 1, _full(1, 10.0)),
                 _round_file(tmp_path, 2, None, rc=1)]
        rc = perf_gate.main(paths + ["--advisory", "--format", "json",
                                     "--baseline", str(tmp_path / "nope")])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["ok"] is False and payload["advisory"] is True
        assert payload["n_rounds"] == 2
        assert [r["dark"] for r in payload["rounds"]] == [False, True]

    def test_extract_metric_line_takes_the_last(self):
        tail = ('{"metric": "old", "value": 1}\n'
                "junk {not json}\n"
                '{"metric": "new", "value": 2}\n')
        assert perf_gate.extract_metric_line(tail)["metric"] == "new"

    def test_unreadable_path_exits_2(self, tmp_path):
        assert perf_gate.main([str(tmp_path / "missing.json")]) == 2

    def test_bare_metric_record_accepted(self, tmp_path):
        path = tmp_path / "BENCH_r01.json"
        path.write_text(json.dumps(_full(1, 5.0)))
        assert perf_gate.main([str(path),
                               "--baseline", str(tmp_path / "nope")]) == 0


def test_schema_constant_pinned_to_bench():
    """bench.py stamps what perf_gate.py validates — one source of truth,
    two files, this assertion is the weld."""
    sys.path.insert(0, REPO)
    import bench

    assert bench.BENCH_SCHEMA == perf_gate.BENCH_SCHEMA_CURRENT
