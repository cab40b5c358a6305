"""Shared fixtures.

Mirrors the reference's ``python/ray/tests/conftest.py``: ``ray_start_regular``
(single-node init/shutdown per test), ``ray_start_cluster`` (in-process
multi-node). JAX-touching tests force an 8-device virtual CPU mesh so
multi-chip sharding logic runs in CI with no TPU attached (the reference
equivalently fakes GPUs with logical resources).

Every test runs under ``TEST_LIMIT_S`` (below: a ``SIGALRM`` that fails the
test with all threads' stacks, and behind it a watchdog that ends the
process). A test that starts a child and waits for the line it announces
itself with does so through ``announced_child`` (below), never a bare
``readline``.
"""

import contextlib
import dataclasses
import faulthandler
import glob
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

# Must be set before jax ever initializes in this process (and inherited by
# every worker subprocess): tests exercise multi-"chip" sharding on a virtual
# 8-device CPU mesh and never touch an accelerator.
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import psutil  # noqa: E402
import pytest  # noqa: E402

import ray_tpu  # noqa: E402

# The tree under test, wherever it is checked out: what a test puts on the
# PYTHONPATH of a child that has to import ray_tpu.
REPO_ROOT = os.path.dirname(os.path.dirname(ray_tpu.__file__))

# THE time limit of one phase (setup, call, teardown) of one test, and of
# every bounded wait in tests/ (thread joins, a child's first line). Sized
# from whole tier-1 runs under -n 6 on 8 cores: the slowest case took 41 s,
# the next 30 s; 180 s is four times that. A test that truly needs more says
# so with ``@pytest.mark.time_limit(seconds)``.
TEST_LIMIT_S = 180.0
# How long after the limit the watchdog ends the process: room for the
# signal's own failure to unwind through the test's ``finally`` blocks.
WATCHDOG_MARGIN_S = 30.0

#: the phase under its limit now, for the signal handler: (nodeid, phase, limit)
_armed = None
#: True once the limit fired in some phase of the test that runs now
_expired = False
#: when the setup of the test that runs now began (children older than that
#: are not its own)
_began = 0.0
#: the worker's stderr as it was before pytest captured it (pytest_configure)
_real_stderr = None


def _all_stacks() -> str:
    """Every thread's stack, as ``faulthandler`` writes it (it needs a file
    with a descriptor, which a captured ``sys.stderr`` may not be)."""
    with tempfile.TemporaryFile(mode="w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        return f.read()


def _on_alarm(signum, frame):
    global _expired
    _expired = True
    said = "%s (%s) passed its time limit of %g s" % _armed
    sys.stderr.write(f"\n{said}; all threads:\n{_all_stacks()}")
    pytest.fail(f"{said} (the stacks of all threads are on stderr)")


def _time_limited(item, phase):
    """Run one phase of ``item`` under its limit. First line: ``SIGALRM`` in
    the main thread fails the phase where it stands (``pytest.fail`` raises a
    ``BaseException``: no ``except Exception`` in a test swallows it).
    Second line, for a wait no signal breaks (a thread stuck in C holding
    the interpreter lock, ``SIGALRM`` blocked): ``faulthandler``'s watchdog,
    a C thread that needs no interpreter lock, dumps all stacks to the real
    stderr and ends the process with status 1; under xdist the test is then
    reported as the one its worker crashed in, and the worker is replaced.
    Armed per phase, because pytest's own faulthandler plugin cancels the
    watchdog whenever a phase fails."""
    global _armed, _expired, _began
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_LIMIT_S
    if phase == "setup":
        _expired = False
        _began = time.time()
    _armed = (item.nodeid, phase, limit)
    faulthandler.dump_traceback_later(limit + WATCHDOG_MARGIN_S, exit=True, file=_real_stderr)
    on_main = threading.current_thread() is threading.main_thread()
    if on_main:
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        try:
            return (yield)
        finally:
            if phase == "teardown":
                if _expired:
                    _end_what_the_test_left()
                _unlink_orphaned_arenas()
    finally:
        if on_main:
            signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()


def _end_what_the_test_left():
    """After a test that passed its limit: the runtime it left initialised
    and the children it started and left running would poison the next test
    on this worker."""
    with contextlib.suppress(Exception):
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
    children = [c for c in psutil.Process().children(recursive=True) if c.create_time() >= _began - 1.0]
    for child in children:
        with contextlib.suppress(psutil.Error):
            child.kill()
    psutil.wait_procs(children, timeout=10)


def _unlink_orphaned_arenas():
    """A head unlinks its arena (``/dev/shm/rta-<pid in hex>-<random>``) in
    ``Head.shutdown``; one that a test terminates or kills cannot, and the
    segment would outlive the suite. Whoever made a segment is named in it;
    no test attaches by name to the arena of a process that is gone."""
    for path in glob.glob("/dev/shm/rta-*"):
        try:
            maker = int(os.path.basename(path).split("-")[1], 16)
        except (IndexError, ValueError):
            continue
        if not psutil.pid_exists(maker):
            with contextlib.suppress(OSError):
                os.unlink(path)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    return (yield from _time_limited(item, "setup"))


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    return (yield from _time_limited(item, "call"))


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_teardown(item):
    return (yield from _time_limited(item, "teardown"))


def join_all(threads, limit=TEST_LIMIT_S):
    """Join ``threads`` within ``limit`` seconds altogether; one that still
    runs then fails the caller (a bare ``join()`` would wait for good)."""
    deadline = time.monotonic() + limit
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
        assert not t.is_alive(), f"thread {t.name} still runs {limit:g} s after the join began"


# How long a child may take to print the line it announces itself with
# (a cold interpreter that imports and initialises ray_tpu: 1-3 s idle,
# under 20 s beside a whole suite).
CHILD_FIRST_LINE_S = 60.0


@contextlib.contextmanager
def announced_child(cmd, announces, *, env=None, limit=CHILD_FIRST_LINE_S, stdin=None):
    """Start ``cmd``, wait at most ``limit`` seconds for the first line of
    its stdout, which must contain ``announces``, and yield ``(process,
    line)``. A child that prints nothing, something else, or dies fails the
    caller with its exit code and its stderr. The child is gone when the
    block is left: terminated, and killed if that does not end it."""
    with tempfile.TemporaryFile(mode="w+") as err:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stdin=stdin, stderr=err, text=True, env=env)
        try:
            first = []
            reader = threading.Thread(target=lambda: first.append(proc.stdout.readline()), daemon=True)
            reader.start()
            reader.join(limit)
            line = first[0] if first else None
            if line is None or announces not in line:
                code = proc.poll()
                proc.kill()
                err.seek(0)
                pytest.fail(
                    f"child {cmd!r} did not announce itself with {announces!r} within {limit:g} s: first line "
                    f"{line!r}, exit code {'none (it still ran)' if code is None else code}, stderr:\n{err.read()}"
                )
            yield proc, line
        finally:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            # a reader still in readline (a grandchild holds the pipe open)
            # holds the pipe's lock: closing under it would wait for good
            if proc.stdin is not None:
                proc.stdin.close()
            if not reader.is_alive():
                proc.stdout.close()
            err.seek(0)
            sys.stderr.write(err.read())


@contextlib.contextmanager
def tcp_head_child(reconnect_grace_s=None):
    """A head in a process of its own that serves TCP; yields ``host:port``.
    Driver and head share a fresh cluster secret through ``RAY_TPU_AUTHKEY``
    for the time of the block."""
    key = os.urandom(16).hex()
    env = dict(os.environ, RAY_TPU_AUTHKEY=key, RAY_TPU_HEALTH_CHECK_INTERVAL_S="0.2")
    if reconnect_grace_s is not None:
        env["RAY_TPU_CLIENT_RECONNECT_GRACE_S"] = str(reconnect_grace_s)
    script = (
        "import ray_tpu, time;"
        "ray_tpu.init(num_cpus=2);"
        "from ray_tpu._private.runtime import get_ctx;"
        "h, p = get_ctx().head.listen_tcp('127.0.0.1', 0);"
        "print(f'ADDR {h}:{p}', flush=True);"
        f"time.sleep({TEST_LIMIT_S})"
    )
    with announced_child([sys.executable, "-c", script], "ADDR ", env=env) as (_, line):
        os.environ["RAY_TPU_AUTHKEY"] = key
        try:
            yield line.split()[1]
        finally:
            os.environ.pop("RAY_TPU_AUTHKEY", None)
            if ray_tpu.is_initialized():
                ray_tpu.shutdown()


@pytest.fixture(scope="module", autouse=True)
def _config_ends_with_the_file():
    """``init(_system_config=...)`` writes the PROCESS's ``GLOBAL_CONFIG`` and
    ``shutdown()`` leaves it so: a file's overrides end with the file, or the
    next file on this xdist worker inherits them (test_worker_timeout's 2 s
    registration deadline, met by test_gc_deadlock's eight spawns under a GC
    storm on a loaded box, was ROADMAP D7's "load flake")."""
    from ray_tpu._private.config import GLOBAL_CONFIG

    found = {f.name: getattr(GLOBAL_CONFIG, f.name) for f in dataclasses.fields(GLOBAL_CONFIG)}
    yield
    for name, value in found.items():
        setattr(GLOBAL_CONFIG, name, value)


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    ray_tpu.init(num_cpus=2, num_tpus=0)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster_holder = []

    def factory(**head_args):
        cluster = Cluster(initialize_head=True, head_node_args=head_args)
        cluster_holder.append(cluster)
        return cluster

    yield factory
    for c in cluster_holder:
        c.shutdown()


def pytest_configure(config):
    global _real_stderr
    config.addinivalue_line("markers", "slow: long-running learning tests")
    config.addinivalue_line(
        "markers", f"time_limit(seconds): this test's own limit in place of the {TEST_LIMIT_S:g} s every test gets"
    )
    # capture is suspended while plugins are configured: 2 is the real stderr
    _real_stderr = os.fdopen(os.dup(2), "w")


def pytest_runtest_logreport(report):
    """Failures land in the flight recorder too, so the flushed ring
    interleaves 'which test failed' with the runtime events around it."""
    if report.failed and report.when == "call":
        try:
            from ray_tpu._private import events

            events.record("ci.test_failed", test=report.nodeid)
        except Exception:
            pass


def pytest_sessionfinish(session, exitstatus):
    """On a failing run, flush THIS process's flight-recorder ring to
    ``RAY_TPU_EVENTS_DIR`` so CI can upload it as a postmortem artifact
    next to the worker rings (those crash-flush themselves on the SIGTERM
    that kills them — _private/events.py).  A green run writes nothing."""
    if exitstatus == 0:
        return
    try:
        from ray_tpu._private import events

        events.flush(reason=f"pytest-exit-{exitstatus}")
    except Exception:
        pass  # never let observability turn a test failure into an error


# the BENCH_r06 spin canary, shared by the load-tolerant tests
# (test_worker_forkserver's spawn wave, test_multihost's CLI roundtrip):
# integer adds per second — this box idles at ~24-29 Mops (BENCH_r06-r08),
# a saturated run measures <10. A skip by the canary means that a loaded and
# an idle run do not execute the same tests.
SPIN_CANARY_FLOOR_MOPS = 12.0


def spin_mops(n: int = 2_000_000) -> float:
    import time as _time

    t0 = _time.perf_counter()
    x = 0
    for i in range(n):
        x += i
    return n / (_time.perf_counter() - t0) / 1e6
