"""Streaming generator returns: num_returns="streaming" yields per-item
ObjectRefs while the task runs.

Reference: ObjectRefGenerator + streaming-generator reporting
(``python/ray/_raylet.pyx:1230``) and the streaming return bookkeeping in
``src/ray/core_worker/task_manager.cc`` — items become objects as they are
produced, consumers iterate with backpressure, mid-stream errors surface at
the point of consumption."""

import time

import pytest

import ray_tpu


def test_roundtrip_and_laziness(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * i

    g = gen.remote(5)
    assert isinstance(g, ray_tpu.ObjectRefGenerator)
    vals = [ray_tpu.get(ref, timeout=30) for ref in g]
    assert vals == [0, 1, 4, 9, 16]


def test_items_arrive_before_task_completes(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def slow_tail():
        yield "first"
        time.sleep(5.0)
        yield "last"

    g = slow_tail.remote()
    t0 = time.monotonic()
    first = ray_tpu.get(next(iter(g)), timeout=30)
    assert first == "first"
    # the first item must arrive long before the producer finishes
    assert time.monotonic() - t0 < 4.0
    g.close()


def test_error_mid_stream(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def boom():
        yield 1
        yield 2
        raise ValueError("stream exploded")

    g = boom.remote()
    it = iter(g)
    assert ray_tpu.get(next(it), timeout=30) == 1
    assert ray_tpu.get(next(it), timeout=30) == 2
    with pytest.raises(ValueError, match="stream exploded"):
        next(it)


def test_function_error_before_first_yield(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def notgen():
        return 42  # not an iterable -> typed error at consumption

    with pytest.raises(TypeError, match="streaming"):
        next(iter(notgen.remote()))


def test_backpressure_bounds_producer(ray_start_regular):
    @ray_tpu.remote
    class Progress:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1

        def value(self):
            return self.n

    p = Progress.options(name="prog").remote()
    ray_tpu.get(p.value.remote(), timeout=30)

    @ray_tpu.remote(num_returns="streaming")
    def firehose():
        import ray_tpu as rt

        prog = rt.get_actor("prog")
        for i in range(100):
            prog.bump.remote()
            yield i

    from ray_tpu._private.config import GLOBAL_CONFIG

    cap = GLOBAL_CONFIG.streaming_backpressure_items
    g = firehose.remote()
    it = iter(g)
    ray_tpu.get(next(it), timeout=30)  # consume exactly one item
    time.sleep(1.0)  # give an unbounded producer time to run away
    produced = ray_tpu.get(p.value.remote(), timeout=30)
    # consumed 1, so the producer must be paused within its window
    assert produced <= 1 + cap + 2, f"producer ran {produced} items ahead"
    # drain: everything still arrives in order
    rest = [ray_tpu.get(r, timeout=30) for r in it]
    assert rest == list(range(1, 100))


def test_dispose_cancels_running_producer(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def endless():
        i = 0
        while True:
            yield i
            i += 1

    g = endless.remote()
    it = iter(g)
    assert ray_tpu.get(next(it), timeout=30) == 0
    g.close()  # consumer walks away -> producer must be cancelled
    from ray_tpu._private.runtime import get_ctx

    head = get_ctx().head
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        with head.lock:
            if not head.tasks:
                break
        time.sleep(0.1)
    with head.lock:
        assert not head.tasks, "producer still running after dispose"


def test_data_pipeline_starts_before_read_finishes(ray_start_regular):
    """A Data map stage consumes a streaming read upstream: the first
    bundle flows downstream while the datasource is still producing
    (reference: read tasks as streaming generators feeding the executor)."""
    import numpy as np

    import ray_tpu.data as rdata
    from ray_tpu.data.datasource import BlockMetadata, Datasource, ReadTask

    def slow_blocks():
        yield {"x": np.arange(10)}
        time.sleep(6.0)  # tail of the read: must NOT gate the first batch
        yield {"x": np.arange(10, 20)}

    class SlowSource(Datasource):
        def get_read_tasks(self, parallelism):
            meta = BlockMetadata(num_rows=None, size_bytes=None, input_files=None)
            return [ReadTask(slow_blocks, meta)]

    ds = rdata.read_datasource(SlowSource()).map(lambda row: {"x": row["x"] + 1})
    t0 = time.monotonic()
    it = ds.iter_batches(batch_size=10)
    first = next(iter(it))
    assert time.monotonic() - t0 < 5.0, "first batch waited for the whole read"
    assert list(first["x"])[:3] == [1, 2, 3]


def test_sync_actor_method_streams(ray_start_regular):
    @ray_tpu.remote
    class Chunker:
        def chunks(self, n):
            for i in range(n):
                yield f"chunk-{i}"

    c = Chunker.remote()
    g = c.chunks.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r, timeout=30) for r in g] == ["chunk-0", "chunk-1", "chunk-2"]


def test_async_actor_method_streams(ray_start_regular):
    @ray_tpu.remote
    class AsyncChunker:
        async def chunks(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * 10

        async def ping(self):
            return "pong"

    c = AsyncChunker.remote()
    g = c.chunks.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r, timeout=30) for r in g] == [0, 10, 20, 30]
    # loop stayed serviceable while the stream ran
    assert ray_tpu.get(c.ping.remote(), timeout=30) == "pong"


def test_an_item_wakes_its_own_stream_only(ray_start_regular):
    """A consumer blocked in ``stream_next`` waits on a condition of ITS
    stream: the items of another stream do not wake it (with one condition
    for all, a replica streaming 900 tokens a second to 120 consumers woke a
    hundred thousand threads a second in the head: PERF.md, PR 35)."""
    import threading

    from ray_tpu._private.runtime import get_ctx

    head = get_ctx().head

    class Counting(threading.Condition):
        notified = 0

        def notify_all(self):
            Counting.notified += 1
            super().notify_all()

    @ray_tpu.remote
    class Gate:
        def __init__(self):
            self.open = False

        def set(self):
            self.open = True

        def is_open(self):
            return self.open

    @ray_tpu.remote(num_returns="streaming")
    def held(gate):
        yield "first"
        while not ray_tpu.get(gate.is_open.remote(), timeout=30):
            time.sleep(0.05)
        yield "second"

    @ray_tpu.remote(num_returns="streaming")
    def busy(n):
        for i in range(n):
            yield i

    gate = Gate.remote()
    slow = held.remote(gate)
    assert ray_tpu.get(next(slow), timeout=30) == "first"
    with head.lock:
        head.streams[slow._task_id]["cond"] = Counting(head.lock)
    got = []
    waiter = threading.Thread(target=lambda: got.append(ray_tpu.get(next(slow), timeout=30)))
    waiter.start()
    time.sleep(0.3)  # the consumer is blocked on the held stream now
    assert [ray_tpu.get(r, timeout=30) for r in busy.remote(40)] == list(range(40))
    assert waiter.is_alive() and Counting.notified == 0
    ray_tpu.get(gate.set.remote(), timeout=30)
    waiter.join(30)
    assert not waiter.is_alive() and got == ["second"] and Counting.notified >= 1
    assert list(slow) == []


# -- values(): the items themselves, pushed (tests/test_stream_pushed.py has the path) --


def test_values_are_the_items_in_order(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * i

    assert list(gen.remote(40).values(timeout=30)) == [i * i for i in range(40)]
    # a stream read by reference and one read by value give the same items
    assert [ray_tpu.get(r, timeout=30) for r in gen.remote(7)] == list(
        gen.remote(7).values(timeout=30))


def test_values_error_mid_stream(ray_start_regular):
    """What was yielded before the failure is delivered, then the
    producer's own exception is raised."""
    @ray_tpu.remote(num_returns="streaming")
    def boom():
        yield 1
        yield 2
        raise ValueError("stream exploded")

    got = []
    with pytest.raises(ValueError, match="stream exploded"):
        for v in boom.remote().values(timeout=30):
            got.append(v)
    assert got == [1, 2]


def test_values_leave_no_object_behind(ray_start_regular):
    """An inline item rides the push and is released where it is handed
    out; an item too large for that comes as a reference, is fetched, and is
    freed with the reference."""
    import numpy as np

    from ray_tpu._private.ids import ObjectID, TaskID
    from ray_tpu._private.runtime import get_ctx

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 7
        yield np.arange(400_000, dtype=np.int64)  # 3.2 MB: not inline
        yield "last"

    g = gen.remote()
    vals = list(g.values(timeout=30))
    assert vals[0] == 7 and vals[2] == "last"
    assert vals[1].shape == (400_000,) and int(vals[1][-1]) == 399_999
    del vals
    head = get_ctx().head
    oids = [ObjectID.for_task_return(TaskID(g._task_id), 1 + i).binary() for i in range(3)]
    deadline = time.time() + 10
    while time.time() < deadline:
        with head.lock:
            left = [o for o in oids if o in head.objects]
        if not left:
            break
        time.sleep(0.05)  # the big item's free rides the gc drain
    assert left == []


def test_serve_stream_reads_values(ray_start_regular):
    """The serve handle's stream iterates values: a generator deployment's
    items arrive whole and in order through it."""
    from ray_tpu import serve

    @serve.deployment
    class Counter:
        def __call__(self, n):
            for i in range(n):
                yield {"i": i}

    try:
        handle = serve.run(Counter.bind(), name="counter")
        got = list(handle.options(stream=True).remote(25))
        assert got == [{"i": i} for i in range(25)]
    finally:
        serve.shutdown()
