"""The time limit of a test (tests/conftest.py ``time_limit``, ``_time_limit``):
one ``pytest`` run in a directory of its own, over a file of four tests, with
the repo's fixture and its limit set to 2 s."""

import os
import re
import subprocess
import sys
import threading

import pytest

from tests import conftest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFTEST = '''
import os
import signal

import pytest

import tests.conftest as repo

repo.TEST_TIME_LIMIT_S = 2.0
_time_limit = repo._time_limit


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    # before any fixture of the test: what the test before it left behind
    handler = signal.getsignal(signal.SIGALRM)
    with open(os.path.join(os.path.dirname(__file__), "left.txt"), "a") as f:
        f.write(f"LEFT {item.name} timer={signal.getitimer(signal.ITIMER_REAL)[0]} default={handler is signal.SIG_DFL}\\n")
'''

TESTS = '''
import signal
import threading
import time


def test_first_is_quick():
    assert 0.0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 2.0


def test_second_waits_on_what_never_comes():
    waiter = threading.Thread(target=time.sleep, args=(5,), name="some-worker", daemon=True)
    waiter.start()
    threading.Event().wait(30)


def test_third_runs_after_it():
    assert 1.0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 2.0   # armed anew: the second used its own up


def test_fourth_swallows_exceptions():
    try:
        time.sleep(30)
    except Exception:
        pass
'''


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    root = tmp_path_factory.mktemp("time_limit")
    (root / "conftest.py").write_text(CONFTEST)
    (root / "test_waits.py").write_text(TESTS)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "test_waits.py", "-v", "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "no:xdist"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )
    return done.returncode, done.stdout + done.stderr, (root / "left.txt").read_text()


def test_a_test_that_waits_fails_by_its_own_id_with_every_threads_stack(run):
    code, out, _ = run
    assert code == 1, out
    assert re.search(r"test_waits\.py::test_second_waits_on_what_never_comes FAILED", out), out
    assert "test_waits.py::test_second_waits_on_what_never_comes took more than 2 s (tests/conftest.py TEST_TIME_LIMIT_S)" in out
    # the main thread where it waited, and the worker it had started
    assert re.search(r"Current thread .*\n(  File .*\n)*  File \".*test_waits\.py\", line \d+ in test_second_waits_on_what_never_comes", out), out
    assert re.search(r"Thread 0x[0-9a-f]+ .*\n(  File .*\n)*  File \".*threading\.py\", line \d+ in run", out), out


def test_the_tests_behind_it_run_and_only_those_that_wait_fail(run):
    code, out, _ = run
    assert "test_waits.py::test_first_is_quick PASSED" in out and "test_waits.py::test_third_runs_after_it PASSED" in out, out
    # a test that catches Exception does not catch the limit
    assert "test_waits.py::test_fourth_swallows_exceptions FAILED" in out, out
    assert re.search(r"2 failed, 2 passed in [0-9.]+s", out), out


def test_the_timer_is_disarmed_and_the_handler_restored_between_tests(run):
    _, out, said = run
    left = re.findall(r"LEFT (\w+) timer=([0-9.]+) default=(\w+)", said)
    assert [name for name, *_ in left] == [
        "test_first_is_quick", "test_second_waits_on_what_never_comes", "test_third_runs_after_it", "test_fourth_swallows_exceptions",
    ], out
    assert all(float(timer) == 0.0 and default == "True" for _, timer, default in left), left


def test_the_limit_is_one_constant_and_this_test_runs_under_it():
    import signal

    assert conftest.TEST_TIME_LIMIT_S == 180.0
    assert 0.0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 180.0                    # the fixture, in this very test


def test_off_the_main_thread_the_limit_is_a_no_op():
    """No handler can be set there: the context does nothing, and says nothing."""
    seen = []

    def body():
        try:
            with conftest.time_limit("somewhere::else"):
                seen.append("ran")
        except BaseException as e:  # noqa: BLE001 - whatever it raised is the failure
            seen.append(e)

    worker = threading.Thread(target=body)
    worker.start()
    worker.join(10)
    assert seen == ["ran"]
