"""The suite's own guards (``tests/conftest.py``): the per-test time limit,
the watchdog behind it, and the bounded "start a child, read the line it
announces itself with" helper. The limit's cases run a child ``pytest`` on a
temporary file beside a copy of the real conftest.
"""

import os
import subprocess
import sys
import time

import psutil
import pytest

import conftest
from conftest import announced_child

SLEEPS_PAST_ITS_LIMIT = """
import subprocess, sys, threading, time, psutil, pytest

def idle_helper(stop):
    stop.wait()

@pytest.mark.time_limit(1)
def test_sleeps():
    stop = threading.Event()
    threading.Thread(target=idle_helper, args=(stop,), daemon=True).start()
    subprocess.Popen([sys.executable, "-c", "import time; time.sleep(300)"])
    try:
        time.sleep(60)
    finally:
        stop.set()

def test_next():
    assert not psutil.Process().children()  # the child test_sleeps left is gone
"""

DEAF_TO_THE_SIGNAL = """
import os, signal, pytest

@pytest.mark.time_limit(1)
def test_deaf():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    os.read(os.pipe()[0], 1)

def test_next():
    pass
"""


def run_child_pytest(tmp_path, source, *options):
    """``pytest`` in a process of its own on ``source``, under the real
    conftest with the watchdog's margin cut to 2 s; ``timeout`` would turn a
    run that does not end by itself into exit code 124."""
    margin = "WATCHDOG_MARGIN_S = 30.0"
    with open(conftest.__file__) as f:
        text = f.read()
    assert margin in text
    (tmp_path / "conftest.py").write_text(text.replace(margin, "WATCHDOG_MARGIN_S = 2.0"))
    (tmp_path / "test_it.py").write_text(source)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["PYTHONPATH"] = conftest.REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        ["timeout", "-k", "5", "120", sys.executable, "-m", "pytest", "test_it.py", "-p", "no:cacheprovider", *options],
        cwd=tmp_path,
        env=env,
        capture_output=True,
        text=True,
    )


def test_a_test_past_its_limit_fails_alone_with_all_stacks(tmp_path):
    run = run_child_pytest(tmp_path, SLEEPS_PAST_ITS_LIMIT, "-p", "no:xdist")
    said = run.stdout + run.stderr
    assert run.returncode == 1, said
    assert "test_it.py::test_sleeps (call) passed its time limit of 1 s" in said
    assert "1 failed, 1 passed" in said
    # the main thread where it slept, and the other thread too
    assert " in test_sleeps" in said and " in idle_helper" in said


@pytest.mark.parametrize("options", [("-p", "no:xdist"), ("-p", "xdist", "-n", "1")], ids=["alone", "xdist"])
def test_a_wait_no_signal_breaks_ends_through_the_watchdog(tmp_path, options):
    run = run_child_pytest(tmp_path, DEAF_TO_THE_SIGNAL, *options)
    said = run.stdout + run.stderr
    assert run.returncode == 1, said
    assert "Timeout (0:00:03)" in said and " in test_deaf" in said
    if "-n" in options:  # the worker is replaced and the file's next test runs
        assert "1 failed, 1 passed" in said


def test_a_child_that_never_prints_fails_within_its_limit():
    silent = "import sys, time; print('I print nothing', file=sys.stderr, flush=True); time.sleep(60)"
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as failure:
        with announced_child([sys.executable, "-c", silent], "READY", limit=3.0):
            pytest.fail("the block ran without an announcement")
    assert time.monotonic() - began < 30
    assert "within 3 s" in str(failure.value) and "I print nothing" in str(failure.value)
    assert not [c for c in psutil.Process().children() if silent in c.cmdline()]
