"""Rehearsal of ``sim.fedavg.sdar.1chip`` at the tiny preset on the CPU: a cell, a
configuration, a traffic mix, a driver kind, a reference and seven per-layer metrics
added as files of their own (``tiny_benchmark_sdar.json``, ``configs/tiny-sdar.json``,
``traffic/tiny.fedavg.sdar.json``) without an edit to the harness.  Run by hand, as the
rest of ``benchmark/tests``.

Each case is a process of its own (``drive_sdar.py``), as a benchmark run is."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
ENV = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")


def drive(fault=None, trace="0"):
    cmd = [sys.executable, os.path.join(HERE, "drive_sdar.py"), "--trace", trace]
    if fault:
        cmd += ["--fault", fault]
    done = subprocess.run(cmd, env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_sound_run_is_correct():
    result, err = drive()
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["compilations_in_window"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    for name, entry in result["compared"].items():
        assert f"compared {name}:" in err and entry["value"] <= entry["limit"]


def test_traced_run_reports_what_it_can_read_off_the_chip():
    result, err = drive(trace="1")
    assert result["correct"] is True, err[-2000:]
    # no device plane in a CPU trace: the trace's readers stay silent, never 0
    assert set(result["metrics"]) == {"sdar.step.mfu", "sdar.moe.local_assignment_share",
                                      "sdar.moe.expert_load_max_over_mean"}
    share = result["metrics"]["sdar.moe.local_assignment_share"]["value"]
    assert 2.0 < share < 40.0  # 4 of 32 experts held: 12.5 % under even routing


@pytest.mark.parametrize("fault", ["sim_state_unchanged", "sim_half_batch"])
def test_planted_fault_is_not_correct(fault):
    result, err = drive(fault=fault)
    assert result["correct"] is False, err[-2000:]


def test_references_of_another_objective_are_not_correct():
    """The planted faults of this cell alone: the reference whose noised queries see the
    earlier noised blocks, and the one without the ``1 / t_b`` weights."""
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_controls_sdar.py"), "--workloads",
         "tiny.sdar", "--seeds", "5", "--require-chip", "0", "--benchmark-json",
         os.path.join(HERE, "tiny_benchmark_sdar.json")],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["sound"]["correct"] is True
    assert line["fault_noised_context"]["correct"] is False
    assert line["fault_no_weights"]["correct"] is False


def test_control_is_not_correct():
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_controls.py"), "--workloads", "tiny.sdar",
         "--seeds", "5", "6", "--require-chip", "0", "--benchmark-json",
         os.path.join(HERE, "tiny_benchmark_sdar.json")],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    for text in done.stdout.strip().splitlines()[-2:]:
        line = json.loads(text)
        verdicts = {k: v["correct"] for k, v in line.items()
                    if isinstance(v, dict) and "correct" in v}
        assert verdicts.pop("sound") is True and verdicts and not any(verdicts.values())
