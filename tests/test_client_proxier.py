"""ray:// client sessions: namespace isolation, reconnect-with-resume,
dirty-disconnect cleanup (VERDICT r4 #7).

Reference: ``python/ray/util/client/server/proxier.py`` — the reference
multiplexes N concurrent ``ray://`` clients through per-client servers with
namespace isolation and reconnect grace. Here the head itself is the proxy
(``ClientSession`` in ``_private/head.py``): every client conn carries a
session token; named actors scope to the session's (anonymous by default)
namespace; a dropped connection resumes with every ref intact when the
client redials with its token, and a client that never comes back has its
refs/actors released after the grace.
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from conftest import announced_child, tcp_head_child


@pytest.fixture
def tcp_head():
    with tcp_head_child(reconnect_grace_s=2) as addr:
        yield addr


CLIENT_A = """
import os, ray_tpu
ray_tpu.init(address="ray://{addr}")

@ray_tpu.remote(num_cpus=0)
class Secret:
    def whoami(self): return "client-a"

s = Secret.options(name="secret").remote()
assert ray_tpu.get(s.whoami.remote(), timeout=60) == "client-a"
# visible to OURSELVES in our session namespace
assert ray_tpu.get(ray_tpu.get_actor("secret").whoami.remote(), timeout=30) == "client-a"
print("A-READY", flush=True)
import sys
for line in sys.stdin:
    if line.strip() == "exit":
        break
ray_tpu.shutdown()
"""


def test_two_clients_namespaces_isolated(tcp_head):
    """Client B must not see client A's named actor (each anonymous
    session gets its own namespace), while both share the cluster."""
    # A stays (reading its stdin) until the block ends it
    with announced_child(
        [sys.executable, "-c", CLIENT_A.format(addr=tcp_head)], "A-READY", env=dict(os.environ), stdin=subprocess.PIPE
    ):
        ray_tpu.init(address=f"ray://{tcp_head}")
        try:
            with pytest.raises(ValueError):
                ray_tpu.get_actor("secret")  # A's namespace, not ours

            # but the cluster itself is shared: plain tasks run fine
            @ray_tpu.remote
            def f(x):
                return x + 1

            assert ray_tpu.get(f.remote(1), timeout=60) == 2

            # same-name actor in OUR namespace does not collide with A's
            @ray_tpu.remote(num_cpus=0)
            class Secret:
                def whoami(self):
                    return "client-b"

            s = Secret.options(name="secret").remote()
            assert ray_tpu.get(s.whoami.remote(), timeout=60) == "client-b"
            assert (
                ray_tpu.get(ray_tpu.get_actor("secret").whoami.remote(), timeout=30)
                == "client-b"
            )
        finally:
            ray_tpu.shutdown()


def test_explicit_shared_namespace(tcp_head):
    """Two clients that ASK for the same namespace share names (reference:
    ray.init(namespace=...))."""
    script = (
        "import ray_tpu;"
        f"ray_tpu.init(address='ray://{tcp_head}', namespace='team');"
        "\n@ray_tpu.remote(num_cpus=0)\n"
        "class P:\n"
        "    def ping(self): return 'shared'\n"
        "p = P.options(name='pact', lifetime='detached').remote()\n"
        "import ray_tpu as r\n"
        "assert r.get(p.ping.remote(), timeout=60) == 'shared'\n"
        "print('OK', flush=True)\n"
        "ray_tpu.shutdown()\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env=dict(os.environ),
    )
    assert "OK" in r.stdout, r.stderr[-800:]
    ray_tpu.init(address=f"ray://{tcp_head}", namespace="team")
    try:
        # detached actor registered under "default" (cluster-scoped) —
        # visible from any session via the detached fallback
        h = ray_tpu.get_actor("pact")
        assert ray_tpu.get(h.ping.remote(), timeout=60) == "shared"
    finally:
        ray_tpu.shutdown()


def test_reconnect_resumes_refs(tcp_head):
    """Kill the client's TCP connection mid-session: the context redials
    with its session token and previously-created refs still resolve."""
    ray_tpu.init(address=f"ray://{tcp_head}")
    try:
        from ray_tpu._private.node_agent import shutdown_conn
        from ray_tpu._private.runtime import get_ctx

        ref = ray_tpu.put({"payload": list(range(100))})

        @ray_tpu.remote
        def g():
            return "alive"

        ctx = get_ctx()
        token = ctx.session_token
        assert token
        old_conn = ctx.conn
        shutdown_conn(old_conn)  # violent drop, no goodbye

        deadline = time.monotonic() + 30
        value = None
        while time.monotonic() < deadline:
            try:
                value = ray_tpu.get(ref, timeout=10)
                break
            except Exception:
                time.sleep(0.3)
        assert value == {"payload": list(range(100))}
        assert ctx.session_token == token  # same session, not a fresh one
        assert ray_tpu.get(g.remote(), timeout=60) == "alive"
    finally:
        ray_tpu.shutdown()


def test_dirty_disconnect_cleans_up_session(tcp_head):
    """A client that dies without shutdown loses its session after the
    grace: its named actor is killed and its namespace entry freed."""
    script = (
        "import os, ray_tpu;"
        f"ray_tpu.init(address='ray://{tcp_head}', namespace='dirty');"
        "\n@ray_tpu.remote(num_cpus=0)\n"
        "class D:\n"
        "    def ping(self): return 1\n"
        "d = D.options(name='doomed').remote()\n"
        "assert ray_tpu.get(d.ping.remote(), timeout=60) == 1\n"
        "print('UP', flush=True)\n"
        "os._exit(1)\n"  # dirty: no shutdown, no frees
    )
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=120,
        env=dict(os.environ),
    )
    assert "UP" in r.stdout, r.stderr[-800:]

    ray_tpu.init(address=f"ray://{tcp_head}", namespace="dirty")
    try:
        # same explicit namespace: the actor is visible until the grace
        # (2s in this fixture) expires, then the head kills it
        deadline = time.monotonic() + 30
        gone = False
        while time.monotonic() < deadline:
            try:
                h = ray_tpu.get_actor("doomed")
                ray_tpu.get(h.ping.remote(), timeout=5)
                time.sleep(0.5)
            except Exception:
                gone = True
                break
        assert gone, "dirty client's actor survived the reconnect grace"
    finally:
        ray_tpu.shutdown()


def test_worker_tasks_inherit_namespace():
    """A plain task submitted from a namespaced driver resolves named
    actors in the DRIVER's namespace (reference: job-scoped namespaces are
    inherited by workers)."""
    ray_tpu.init(num_cpus=2, namespace="teamspace")
    try:

        @ray_tpu.remote(num_cpus=0)
        class N:
            def who(self):
                return "ns-actor"

        keep = N.options(name="scoped").remote()  # noqa: F841 - a dropped
        # handle would GC the actor (num_handles -> 0) before lookup runs

        @ray_tpu.remote
        def lookup():
            return ray_tpu.get(
                ray_tpu.get_actor("scoped").who.remote(), timeout=30
            )

        assert ray_tpu.get(lookup.remote(), timeout=60) == "ns-actor"

        @ray_tpu.remote
        def create_inside():
            @ray_tpu.remote(num_cpus=0)
            class M:
                def who(self):
                    return "made-in-task"

            import ray_tpu as r

            h = M.options(name="task-made", lifetime="detached").remote()
            r.get(h.who.remote(), timeout=30)  # ensure ALIVE before return
            return True

        assert ray_tpu.get(create_inside.remote(), timeout=60)
        # a DETACHED actor created inside the task outlives the task and
        # registers cluster-scoped — visible from the driver
        assert (
            ray_tpu.get(ray_tpu.get_actor("task-made").who.remote(), timeout=30)
            == "made-in-task"
        )
    finally:
        ray_tpu.shutdown()
