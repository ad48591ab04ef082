"""The limit tests/conftest.py puts on every test (`TEST_LIMIT_S`): a test
that outstays it fails by its node id with every thread's stack printed, and
one that ends in time leaves nothing armed."""

import os
import signal
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_alarm = pytest.mark.skipif(not hasattr(signal, "SIGALRM"),
                                 reason="no SIGALRM on this platform")


def run_with_a_limit_of_one_second(tmp_path, body):
    """pytest in a process of its own on a file holding ``body``, under this
    repository's hooks with the limit patched down to 1 s."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent("""
        import tests.conftest as ours
        from tests.conftest import (pytest_configure, pytest_runtest_call,
                                    pytest_runtest_setup)  # noqa: F401
        ours.TEST_LIMIT_S = 1
        """))
    (tmp_path / "test_waits.py").write_text(textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "pytest", "test_waits.py", "-q", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)


@needs_alarm
@pytest.mark.parametrize("wait", [
    "time.sleep(30)",
    "subprocess.run([sys.executable, '-c', 'import time; time.sleep(30)'])",
    "held.acquire()",
    "queue.Queue().get()"], ids=["sleep", "subprocess", "lock", "queue"])
def test_a_test_that_outstays_the_limit_fails_by_its_node_id(tmp_path, wait):
    done = run_with_a_limit_of_one_second(tmp_path, f"""
        import queue, subprocess, sys, threading, time

        def test_ends():
            pass

        def test_waits():
            held = threading.Lock()
            held.acquire()
            {wait}

        def test_ends_after_it():
            pass
        """)
    assert done.returncode == 1, done.stdout + done.stderr
    assert "test_waits.py::test_waits ran past its 1 s limit" in done.stdout
    assert "1 failed, 2 passed" in done.stdout
    # Every thread's stack, on the terminal's stderr and not in the capture.
    assert "most recent call first" in done.stderr
    assert "test_waits" in done.stderr


@needs_alarm
def test_a_fixture_that_outstays_the_limit_fails_its_test_by_name(tmp_path):
    done = run_with_a_limit_of_one_second(tmp_path, """
        import time

        import pytest

        @pytest.fixture(scope="module")
        def slow():
            time.sleep(30)

        def test_reads_it(slow):
            pass
        """)
    assert done.returncode == 1, done.stdout + done.stderr
    assert "test_waits.py::test_reads_it ran past its 1 s limit" in done.stdout


@needs_alarm
def test_a_test_that_ends_in_time_leaves_no_alarm_armed(tmp_path):
    """Inside a test the alarm is armed; after it (a fixture's teardown reads
    the timer) it is not, and the signal has its handler back."""
    done = run_with_a_limit_of_one_second(tmp_path, """
        import signal

        import pytest

        @pytest.fixture(scope="module")
        def afterwards():
            yield
            assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
            assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL

        def test_is_under_the_limit(afterwards):
            left, _ = signal.getitimer(signal.ITIMER_REAL)
            assert 0.0 < left <= 1.0
        """)
    assert done.returncode == 0, done.stdout + done.stderr
    assert "1 passed" in done.stdout


@needs_alarm
def test_this_suites_own_tests_run_under_the_limit_and_leave_it_disarmed():
    from tests import conftest

    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0.0 < left <= conftest.TEST_LIMIT_S == 300
