"""The per-test time limit of ``tests/conftest.py``: a test that runs past it
fails under its own name, and the session goes on to the next test, in one
process and in an xdist worker alike."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("workers", [[], ["-p", "xdist", "-n", "1"]], ids=["plain", "xdist"])
def test_a_test_past_its_limit_fails_by_name_and_the_session_goes_on(tmp_path, workers):
    (tmp_path / "test_sleeper.py").write_text(textwrap.dedent("""
        import time

        def test_sleeps_past_the_limit():
            time.sleep(60)

        def test_after_it():
            pass
    """))
    # loaded after the conftest in every process that runs tests: its limit down to 1 s
    (tmp_path / "limit_one_second.py").write_text(
        "import tests.conftest\n\ntests.conftest.TEST_TIME_LIMIT_S = 1\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([ROOT, str(tmp_path)]))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", "--rootdir", str(tmp_path),
         "-p", "tests.conftest", "-p", "limit_one_second", "-p", "no:cacheprovider",
         "-p", "no:randomly", *workers],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    out = done.stdout + done.stderr
    assert done.returncode == 1, out
    assert "FAILED test_sleeper.py::test_sleeps_past_the_limit" in out, out
    assert "test_sleeper.py::test_sleeps_past_the_limit ran past its 1 s limit" in out, out
    assert "1 failed, 1 passed" in out, out
