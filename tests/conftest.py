"""Shared fixtures.

Mirrors the reference's ``python/ray/tests/conftest.py``: ``ray_start_regular``
(single-node init/shutdown per test), ``ray_start_cluster`` (in-process
multi-node). JAX-touching tests force an 8-device virtual CPU mesh so
multi-chip sharding logic runs in CI with no TPU attached (the reference
equivalently fakes GPUs with logical resources).
"""

import os

# Must be set before jax ever initializes in this process (and inherited by
# every worker subprocess): tests exercise multi-"chip" sharding on a virtual
# 8-device CPU mesh and never touch an accelerator.
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


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
    config.addinivalue_line("markers", "slow: long-running learning tests")


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
# a saturated run measures <10
SPIN_CANARY_FLOOR_MOPS = 12.0


def spin_mops(n: int = 2_000_000) -> float:
    import time as _time

    t0 = _time.perf_counter()
    x = 0
    for i in range(n):
        x += i
    return n / (_time.perf_counter() - t0) / 1e6
