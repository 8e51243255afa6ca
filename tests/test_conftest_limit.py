"""The limit `tests/conftest.py` puts on every test's call: a test that hangs
fails by its name, with every thread's stack in its report, and the run goes
on. Run as a pytest of its own over three tests in a temp directory whose
conftest takes the hook from this repo's and patches its limit to 1 s."""

import os
import subprocess
import sys

import pytest

_CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")

_TEMP_CONFTEST = f"""
import importlib.util
import signal

import pytest

spec = importlib.util.spec_from_file_location("repo_conftest", {_CONFTEST!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest._TEST_LIMIT_S = 1.0
pytest_runtest_call = repo_conftest.pytest_runtest_call


@pytest.fixture(autouse=True)
def no_timer_is_left_armed():
    yield
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL
"""

_TEMP_TESTS = """
import threading
import time


def _stuck_in_a_retry_loop():
    while True:
        try:
            time.sleep(30)
        except Exception:
            pass


def test_a_sleeper():
    threading.Thread(target=time.sleep, args=(5,), name="a-helper-thread", daemon=True).start()
    _stuck_in_a_retry_loop()


def test_the_one_after_it():
    assert threading.current_thread() is threading.main_thread()


def test_a_quick_one_under_the_limit():
    time.sleep(0.05)
"""


@pytest.mark.parametrize("how", [["-p", "no:xdist"], ["-p", "xdist", "-n", "1"]], ids=["serial", "xdist-worker"])
def test_a_test_that_hangs_fails_by_its_name_and_the_run_goes_on(tmp_path, how):
    (tmp_path / "conftest.py").write_text(_TEMP_CONFTEST)
    (tmp_path / "test_three.py").write_text(_TEMP_TESTS)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", "-p", "no:cacheprovider", "-p", "no:randomly", *how],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(tmp_path),
    )
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "1 failed, 2 passed" in out, out  # the run went on, and no teardown found a timer armed
    assert "FAILED test_three.py::test_a_sleeper" in out, out
    assert "test_three.py::test_a_sleeper ran past its limit of 1 s; every thread's stack:" in out, out
    # the stack it stood in, and the helper thread's beside it
    assert "_stuck_in_a_retry_loop" in out and "Thread 0x" in out and "Current thread" in out, out
