"""Regression tests for GC-reentrancy deadlocks.

Round-3 postmortem: a worker-IO thread held the head lock through
``rpc_create_actor -> ... -> _start_actor_on -> Thread.start()``; the child
thread's bootstrap hit a GC tick that ran ``ObjectRef.__del__`` ->
``free_ref_async`` -> a SYNCHRONOUS ``head.remove_ref`` -> blocked on the held
head lock, while the parent sat in ``Thread.start()`` waiting for the child.
The fix (a) routes every ``__del__``-reachable runtime touch through a
reentrant ``SimpleQueue`` drained off-thread (reference: the reference never
blocks in a destructor — decrements post to the io_context,
``src/ray/core_worker/reference_count.h:61``), and (b) moves worker spawning
to a dispatcher thread so ``Thread.start()`` never runs under the head lock.
"""

import gc
import threading
import time

import ray_tpu
from ray_tpu._private import runtime


def test_del_never_blocks_on_head_lock(ray_start_regular):
    """Deterministic replay of the round-3 wedge: drop an owned ObjectRef in
    a side thread WHILE this thread holds the head lock. Pre-fix, the side
    thread blocked in remove_ref forever; post-fix, __del__ only enqueues."""
    ctx = runtime.get_ctx()
    box = [ray_tpu.put(b"y" * 32)]
    oid = box[0].binary()
    done = threading.Event()

    def drop():
        box.pop()  # last handle -> __del__ fires here
        gc.collect()
        done.set()

    with ctx.head.lock:
        t = threading.Thread(target=drop, daemon=True)
        t.start()
        assert done.wait(timeout=10), (
            "ObjectRef.__del__ blocked while the head lock was held "
            "(GC-reentrancy deadlock regression)"
        )
    # the drain thread now performs the real decrement -> eviction
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with ctx.head.lock:
            ent = ctx.head.objects.get(oid)
            if ent is None or ent.refcount <= 0:
                return
        time.sleep(0.05)
    raise AssertionError("queued free was never drained (refcount still held)")


def test_actor_spawn_under_gc_storm(ray_start_regular):
    """Allocation storm with owned refs dying inside reference cycles while
    actors spawn: GC ticks land in arbitrary threads (including worker-spawn
    bootstraps). Pre-fix this wedged GC-timing-dependently; the whole flow
    must complete within the deadline."""

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    stop, rounds = threading.Event(), [0]

    def storm():
        while not stop.is_set():
            rounds[0] += 1
            refs = [ray_tpu.put(b"x" * 64) for _ in range(32)]
            cyc = []
            for r in refs:
                d = {"ref": r}
                d["self"] = d  # cycle -> only the collector frees it
                cyc.append(d)
            del refs, cyc
            gc.collect()

    old = gc.get_threshold()
    gc.set_threshold(5, 2, 2)  # GC on nearly every allocation, every thread
    storm_t = threading.Thread(target=storm, daemon=True)
    storm_t.start()
    try:
        ok = []

        def spawn_and_call():
            actors = [A.remote() for _ in range(8)]
            assert ray_tpu.get([a.ping.remote() for a in actors]) == [1] * 8
            for a in actors:
                ray_tpu.kill(a)
            ok.append(True)

        w = threading.Thread(target=spawn_and_call, daemon=True)
        w.start()
        # under the test's own limit (conftest.TEST_LIMIT_S), so that a wedge
        # fails HERE, with this message; a worker's registration deadline is
        # the configuration's 30 s, whatever an earlier file on this xdist
        # worker set (conftest._config_ends_with_the_file)
        w.join(timeout=150)
        assert ok, "actor spawn wedged under GC storm (__del__ deadlock?)"
        assert rounds[0] > 0, "the storm never ran beside the spawns"
    finally:
        stop.set()
        gc.set_threshold(*old)
        storm_t.join(timeout=10)
