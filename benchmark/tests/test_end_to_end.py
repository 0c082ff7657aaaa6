"""Rehearsals 1 and 2 and the planted faults, at the tiny preset on the CPU —
itself a cell, a configuration and three traffic mixes added as files of
their own (``configs/tiny.json``, ``traffic/tiny.*.json`` — one of them a
sampled cohort with odd shards, which is data alone —
``tests/tiny_benchmark.json``) without an edit to the harness.

Each case is a process of its own (``drive.py``), as a benchmark run is."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def drive(workload, fault=None, trace="0", devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(HERE, "drive.py"), "--workload", workload, "--trace", trace]
    if fault:
        cmd += ["--fault", fault]
    done = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


@pytest.mark.parametrize("workload,devices", [("tiny.sim", 1), ("tiny.sim4", 4), ("tiny.sampled", 1), ("tiny.sampled4", 4)])
def test_sound_run_is_correct(workload, devices):
    result, err = drive(workload, devices=devices)
    assert result["correct"] is True, err[-2000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["compilations_in_window"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert result["device"]["count"] == devices
    assert list(result)[-1] == "compared"
    for name, entry in result["compared"].items():
        assert f"compared {name}:" in err and entry["value"] <= entry["limit"]


def test_traced_run_reports_layer_metrics_it_can_read():
    result, err = drive("tiny.sim", trace="1")
    assert result["correct"] is True, err[-2000:]
    # no device plane in a CPU trace: the trace's readers stay silent, never 0
    assert set(result["metrics"]) == {"startup.compile_s", "round.wall_s_median", "step.mfu"}
    assert "breakdown" in result and "window_s" in result["device"]


@pytest.mark.parametrize("workload,fault,devices", [
    ("tiny.sim", "sim_state_unchanged", 1), ("tiny.sim", "sim_half_batch", 1),
    ("tiny.sim4", "sim_no_exchange", 4),
    ("tiny.sampled", "sim_half_batch", 1), ("tiny.sampled4", "sim_no_exchange", 4)])
def test_planted_fault_is_not_correct(workload, fault, devices):
    result, err = drive(workload, fault=fault, devices=devices)
    assert result["correct"] is False, err[-2000:]


def test_refuses_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "sim.fedavg.1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "no published peak for device_kind" in done.stderr


def test_read_controls_judges_by_each_cells_limits():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "read_controls.py"), "--workloads", "tiny.sim", "tiny.sim4",
         "--seeds", "5", "--require-chip", "0", "--benchmark-json", os.path.join(HERE, "tiny_benchmark.json")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    assert [line["workload"] for line in lines] == ["tiny.sim", "tiny.sim4"]
    for line in lines:
        verdicts = {k: v["correct"] for k, v in line.items() if isinstance(v, dict) and "correct" in v}
        assert verdicts.pop("sound") is True and verdicts and not any(verdicts.values())
    assert "fault_no_exchange" in lines[1] and "fault_no_exchange" not in lines[0]
