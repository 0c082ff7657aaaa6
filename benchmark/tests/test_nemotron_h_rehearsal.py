"""Rehearsal of ``sim.fedavg.nemotron-nano.1chip`` at the tiny preset on the CPU: a cell, a
configuration, a traffic mix, a driver kind, a reference and eight per-layer metrics added
as files of their own (``tiny_benchmark_nemotron_h.json``, ``configs/tiny-nemotron-h.json``,
``traffic/tiny.fedavg.nemotron.json``) without an edit to the harness.  Run by hand, as the
rest of ``benchmark/tests``.

Each case is a process of its own (``drive_nemotron_h.py``), as a benchmark run is."""

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
    cmd = [sys.executable, os.path.join(HERE, "drive_nemotron_h.py"), "--trace", trace]
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
    assert set(result["metrics"]) == {"nemotron_h.step.mfu", "nemotron_h.moe.local_assignment_share",
                                      "nemotron_h.moe.expert_load_max_over_mean"}
    share = result["metrics"]["nemotron_h.moe.local_assignment_share"]["value"]
    assert 1.0 < share < 40.0  # 2 of 32 experts held: 6.25 % under even routing


@pytest.mark.parametrize("fault", ["sim_state_unchanged", "sim_half_batch"])
def test_planted_fault_is_not_correct(fault):
    result, err = drive(fault=fault)
    assert result["correct"] is False, err[-2000:]


def test_controls_and_the_models_own_faults_are_not_correct():
    """int8 and float8 one step below the preset's float32 are ``read_controls``' bfloat16
    for a float32 preset; the model's own faults: the skip ``D x``, the gate ``SiLU(z)``
    and ``relu^2`` each left out."""
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_controls_nemotron_h.py"), "--workloads",
         "tiny.nemotron", "--seeds", "5", "--require-chip", "0", "--benchmark-json",
         os.path.join(HERE, "tiny_benchmark_nemotron_h.json")],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["sound"]["correct"] is True
    for fault in ("fault_no_D", "fault_no_gate", "fault_relu", "control_bfloat16"):
        assert line[fault]["correct"] is False, fault
