"""quick_start/ parity (reference python/quick_start/{parrot,octopus,beehive}):
the beginner entry scripts must actually run."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QS = os.path.join(ROOT, "quick_start")


def _run_script(path, cfg):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    return subprocess.run(
        [sys.executable, path, "--cf", cfg],
        cwd=os.path.dirname(path), env=env, capture_output=True, text=True,
        timeout=300,
    )


@pytest.mark.heavy
@pytest.mark.parametrize("script", [
    "fedavg_mnist_lr_one_line_example.py",
    "fedavg_mnist_lr_step_by_step_example.py",
    "fedavg_mnist_lr_custom_data_and_model_example.py",
])
def test_parrot_quick_start(script):
    path = os.path.join(QS, "parrot", script)
    cfg = os.path.join(QS, "parrot", "fedml_config.yaml")
    proc = _run_script(path, cfg)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_quick_start_tree_complete():
    assert os.path.isfile(os.path.join(QS, "octopus", "server.py"))
    assert os.path.isfile(os.path.join(QS, "octopus", "client.py"))
    assert os.path.isfile(os.path.join(QS, "beehive", "server.py"))
