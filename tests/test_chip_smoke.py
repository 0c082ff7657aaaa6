"""chip_smoke.py's contract off the chip: it refuses to run without a TPU
(from the normal entry point, before any data is generated), it needs the
repo around it, and a leg that raises fails the run.  What it proves ON the
chip is recorded in CHANGES.md / PERF.md by the PR that ran it."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    t0 = time.time()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    return proc, time.time() - t0


def test_refuses_without_a_tpu_in_seconds_and_prints_no_result():
    proc, seconds = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert seconds < 60
    assert proc.stdout.strip() == ""  # no report, no {"ok": ...} line
    # the refusal is device.get_device honouring device_args.device_type,
    # and it came before the synthetic dataset was generated
    assert "device_args.device_type is 'tpu'" in proc.stderr
    assert "generated synthetic" not in proc.stderr


def test_fails_in_a_directory_that_holds_nothing_else_of_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc, _ = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "fedml_tpu" in proc.stderr


def test_a_leg_that_raises_fails_the_run(monkeypatch, capsys):
    sys.path.insert(0, ROOT)
    import chip_smoke

    # toy width on the CPU so main() gets past its own checks; the legs are
    # stand-ins — the subject is main()'s bookkeeping, not the legs
    for section, key, value in (
            ("device_args", "device_type", "cpu"),
            ("model_args", "model", "lr"),
            ("data_args", "dataset", "mnist"),
            ("data_args", "synthetic_train_size", 400),
            ("train_args", "client_num_in_total", 8),
            ("train_args", "client_num_per_round", 4)):
        monkeypatch.setitem(chip_smoke.ROUND_CONFIG[section], key, value)

    def _boom():
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(chip_smoke, "round_leg",
                        lambda *a: {"round_loss": [1.0]})
    monkeypatch.setattr(chip_smoke, "ring_leg", lambda: {})
    monkeypatch.setattr(chip_smoke, "kernel_leg", _boom)
    assert chip_smoke.main() == 1
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    report = json.loads(lines[-1])["smoke_report"]  # no {"ok": ...} after it
    assert report["failed"] == ["B_kernels"]
    assert "Mosaic refused" in report["legs"]["B_kernels"]["failed"]
    assert "A_round_1dev" in report["legs_run"]  # the other legs still ran

    monkeypatch.setattr(chip_smoke, "kernel_leg", lambda: {})
    assert chip_smoke.main() == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["ok"] is True and set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}


def test_leg_b_holds_the_kda_kernels_and_not_the_xla_path():
    """``kda_errors`` goes through the entry the model calls; where that did not
    lower to ``kda_fwd`` / ``kda_bwd`` (here: no TPU) the leg fails before it
    compares anything, so a pass on the chip is the kernels' pass."""
    sys.path.insert(0, ROOT)
    import chip_smoke

    with pytest.raises(AssertionError, match="kda_fwd / kda_bwd"):
        chip_smoke.kda_errors(128, 2, 128)
