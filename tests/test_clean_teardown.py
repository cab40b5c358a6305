"""Zero-noise teardown (VERDICT r4 #10): a driver that exits — cleanly or
abruptly — must leave NOTHING on stderr. The reference's worker teardown is
silent by design (``python/ray/_private/worker.py`` main_loop); tracebacks
from late-spawning workers mask real shm-lifetime bugs.

These scenarios pin the historical noise sources: shutdown with spawns
mid-flight (register hits a dead head), shutdown with results mid-send
(task_done hits a closed socket), and a dirty ``os._exit`` driver. Each
subprocess's stderr is read to EOF, which by construction waits for every
orphaned worker holding the fd — late prints cannot escape the assertion.
"""

import os
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(os.environ, PYTHONPATH=_REPO_ROOT)

SCENARIOS = {
    "shutdown_mid_spawn": """
import ray_tpu
ray_tpu.init(num_cpus=8)

@ray_tpu.remote
def f(x):
    return x

refs = [f.remote(i) for i in range(16)]
ray_tpu.shutdown()   # immediately: workers mid-fork/registration
""",
    "shutdown_mid_result": """
import time
import ray_tpu
ray_tpu.init(num_cpus=4)

@ray_tpu.remote
def slow(x):
    time.sleep(0.3)
    return bytes(200_000)  # result in flight when the head dies

refs = [slow.remote(i) for i in range(8)]
time.sleep(0.35)
ray_tpu.shutdown()
""",
    "dirty_exit_with_actors": """
import os, time
import ray_tpu
ray_tpu.init(num_cpus=4)

@ray_tpu.remote(num_cpus=0)
class A:
    def ping(self):
        return 1

actors = [A.remote() for _ in range(4)]
refs = [a.ping.remote() for a in actors]
time.sleep(0.1)
os._exit(0)  # no shutdown, no atexit
""",
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_teardown_is_silent(name):
    r = subprocess.run(
        [sys.executable, "-c", SCENARIOS[name]],
        capture_output=True,
        text=True,
        timeout=180,
        env=_ENV,
    )
    assert "Traceback" not in r.stderr, f"{name} stderr:\n{r.stderr[:2000]}"
    assert "Error" not in r.stderr, f"{name} stderr:\n{r.stderr[:2000]}"
