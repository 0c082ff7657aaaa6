"""Rehearsal of the ten readers of PR 35 at the tiny preset on the CPU: ten per-layer
metrics, their shared helper (``benchmark/program_scopes.py``) and a cell that lists them
(``tiny_benchmark_scopes.json``) added as files of their own without an edit to the
harness.  They take their table from the program (``XLASimulator.round_scopes()``); a CPU
trace has no device plane, so each stays silent here and none raises.  Run by hand, as the
rest of ``benchmark/tests``; each case is a process of its own (``drive_scopes.py``)."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
ENV = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
NEW = ("fed_flush.device_ms_per_round", "fed_server_step.device_ms_per_round",
       "fed_gather.device_ms_per_round", "fed_sgd.device_ms_per_round",
       "fed_loss.device_ms_per_round", "lm_head.device_ms_per_round",
       "lm_embed.device_ms_per_round", "lm_mlp.device_ms_per_round",
       "lm_attn.device_ms_per_round", "step.unscoped_device_ms_per_round")


def drive(trace):
    done = subprocess.run([sys.executable, os.path.join(HERE, "drive_scopes.py"), "--trace", trace],
                          env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_the_cell_lists_the_ten_new_names_as_benchmark_json_does():
    with open(os.path.join(HERE, "tiny_benchmark_scopes.json")) as f:
        tiny = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        accepted = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert tiny[name][key] == accepted[name][key], (name, key)


def test_traced_run_keeps_the_ten_readers_silent_off_the_chip():
    result, err = drive("1")
    assert result["correct"] is True, err[-2000:]
    # no device plane in a CPU trace: nothing to join the table to, and none of them raised
    assert set(result["metrics"]) == {"round.wall_s_median"}
    assert "round_scopes() calls: 0" in err


def test_untraced_run_never_asks_the_program_for_its_table():
    result, err = drive("0")
    assert result["correct"] is True and result["compilations_in_window"] == 0
    assert "round_scopes() calls: 0" in err


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_none_without_a_trace(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    reader = importlib.util.module_from_spec(spec)
    sys.path.insert(0, ROOT)
    spec.loader.exec_module(reader)
    sim = types.SimpleNamespace(round_scopes=lambda: pytest.fail("asked for the table"))
    ctx = types.SimpleNamespace(driver=types.SimpleNamespace(sim=sim), trace=None, units=[{}])
    assert reader.read(ctx) is None
    # a program older than the table (the parent commit): no ``round_scopes`` at all
    older = types.SimpleNamespace(driver=types.SimpleNamespace(sim=object()), units=[{}],
                                  trace=types.SimpleNamespace(ops={0: []}, busy_s=1.0))
    assert reader.read(older) is None
