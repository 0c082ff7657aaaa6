"""Test harness: force an 8-device virtual CPU mesh regardless of outer env.

This is how "multi-node" is tested without hardware (SURVEY.md §4 implication):
every sharding/collective test runs over 8 virtual devices on one host; the
chip itself is reached only through ``chip_smoke.py`` / ``bench.py``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Load the shared force-CPU helper WITHOUT importing the fedml_tpu package:
# `from fedml_tpu.utils.platform import ...` would execute fedml_tpu/__init__
# (and its full import graph) before jax is pinned to the CPU — a module-level
# jax.devices()/jnp constant there would then pick the backend first.
import importlib.util as _ilu  # noqa: E402

_spec = _ilu.spec_from_file_location(
    "_fedml_tpu_platform_util",
    os.path.join(os.path.dirname(__file__), os.pardir, "fedml_tpu", "utils", "platform.py"),
)
_mod = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
_mod.force_cpu_backend()

import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: Seconds one test may run. Past it the test fails under its own name and the
#: session goes on, instead of a hang cutting the whole run at its outer limit.
TEST_TIME_LIMIT_S = 600


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Arm SIGALRM around the test's call. Signals reach the main thread alone,
    which is where pytest (and each xdist worker) runs tests; elsewhere the
    test runs unlimited. A test stuck inside one C call fails once it returns."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError(f"{item.nodeid} ran past its {TEST_TIME_LIMIT_S} s limit")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(TEST_TIME_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Security/DP singletons are process-global; isolate tests."""
    yield
    from fedml_tpu.core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
    from fedml_tpu.core.security.fedml_attacker import FedMLAttacker
    from fedml_tpu.core.security.fedml_defender import FedMLDefender

    FedMLDifferentialPrivacy._instance = None
    FedMLAttacker._attacker_instance = None
    FedMLDefender._defender_instance = None


@pytest.fixture
def rng():
    return np.random.RandomState(0)
