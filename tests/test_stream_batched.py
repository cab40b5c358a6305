"""The batched producer path of a streaming task (``_private.stream_sink``):
a body that ADOPTED its stream's sink pushes items from any thread, one
``flush`` sends every stream's items as ONE ``stream_items`` message, and the
head stores and wakes them under one take of its lock.

``tests/test_streaming_generators.py`` pins the per-item path; here an actor
stands for an LLM engine: every open stream is a row, ``step`` pushes one
item to each row from ONE thread and flushes once.
"""

import collections
import queue
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import stream_sink, stream_stats
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.runtime import get_ctx
from ray_tpu.exceptions import RayTaskError

_END = "__end__"


@ray_tpu.remote(max_concurrency=48)
class Rows:
    def __init__(self):
        self.rows = {}  # name -> (sink or None, its inbox)
        self.lock = threading.Lock()

    def row(self, name, adopt=True):
        """A stream.  Adopting, its items come through ``step`` and it
        waits for its end alone; else it yields what ``step`` puts in its
        inbox, one by one (the per-item path)."""
        sink = stream_sink.adopt() if adopt else None
        inbox = queue.SimpleQueue()
        if sink is not None:
            sink.on_cancel(lambda: inbox.put("cancelled"))
        with self.lock:
            self.rows[name] = (sink, inbox)
        try:
            while True:
                item = inbox.get(timeout=60)
                if item == _END:
                    return
                if item == "cancelled":
                    return
                if item == "boom":
                    raise ValueError(f"row {name} failed")
                yield item
        finally:
            with self.lock:
                self.rows.pop(name, None)

    def burst(self, n, flush):
        """A stream that pushes ``n`` items and ends in the same breath."""
        sink = stream_sink.adopt()
        for i in range(n):
            sink.push(i)
        if flush:
            sink.flush()
        return
        yield  # a generator

    def open_rows(self):
        with self.lock:
            return sorted(self.rows, key=str)

    def step(self, k, flush=True):
        """One 'engine step': an item to every open row, ONE flush (here,
        or with ``"soon"`` by the worker's sender thread)."""
        with self.lock:
            rows = list(self.rows.items())
        any_sink = None
        for name, (sink, inbox) in rows:
            if sink is not None:
                sink.push((name, k))
                any_sink = sink
            else:
                inbox.put((name, k))
        if flush and any_sink is not None:
            # the worker's whole outbox, whichever sink asks
            any_sink.flush_soon() if flush == "soon" else any_sink.flush()
        return len(rows)

    def end(self, how=_END):
        with self.lock:
            rows = list(self.rows.values())
        for _sink, inbox in rows:
            inbox.put(how)

    def totals(self):
        snap = stream_stats.snapshot(emit=[])
        out = dict(snap["batch"], **snap["backpressure"])
        out.update(sent=sum(snap["sent"]), wake=sum(snap["wake"]))
        out.update({"ack_" + k: v for k, v in snap["ack"].items()})
        return out


def _gained(actor, before):
    now = ray_tpu.get(actor.totals.remote(), timeout=30)
    return {k: now[k] - before[k] for k in now}


def _open(actor, names, **kw):
    gens = {
        n: actor.row.options(num_returns="streaming").remote(n, **kw) for n in names
    }
    deadline = time.time() + 30
    while len(ray_tpu.get(actor.open_rows.remote(), timeout=30)) < len(names):
        assert time.time() < deadline, "the rows never opened"
        time.sleep(0.02)
    return gens


def _count_messages(head) -> collections.Counter:
    """Count the head's worker messages by kind from here on."""
    seen, orig = collections.Counter(), head._handle_worker_msg

    def counting(conn, wh, remote, msg):
        seen[msg[0]] += 1
        return orig(conn, wh, remote, msg)

    head._handle_worker_msg = counting
    return seen


def _item_ids(gen, n):
    return [ObjectID.for_task_return(TaskID(gen._task_id), 1 + i).binary() for i in range(n)]


def _none_left(head, oids, wait_s=10.0):
    deadline = time.time() + wait_s
    while True:
        with head.lock:
            left = [o for o in oids if o in head.objects]
        if not left or time.time() > deadline:
            return left
        time.sleep(0.05)


@pytest.fixture
def rows(ray_start_regular):
    actor = Rows.remote()
    return actor, ray_tpu.get(actor.totals.remote(), timeout=60)


@pytest.mark.parametrize("flush", [True, "soon"], ids=["here", "by_the_sender"])
def test_a_step_of_32_streams_is_one_message(rows, flush):
    """(a) 32 streams pushed from one thread, ``flush`` a step: every
    stream's items in order, exactly once; one ``stream_items`` a step,
    from the pushing thread or from the sender it wakes (as an engine does)."""
    actor, before = rows
    seen = _count_messages(get_ctx().head)
    gens = _open(actor, range(32))
    steps = 10  # inside the window of 16: nothing is held back
    for k in range(steps):
        assert ray_tpu.get(actor.step.remote(k, flush), timeout=30) == 32
        if flush == "soon":  # the step returned before its message left
            deadline = time.time() + 30
            while _gained(actor, before)["items"] < 32 * (k + 1):
                assert time.time() < deadline, "the sender never flushed"
                time.sleep(0.01)
    ray_tpu.get(actor.end.remote(), timeout=30)
    for name, gen in gens.items():
        assert list(gen.values(timeout=30)) == [(name, k) for k in range(steps)]
    got = _gained(actor, before)
    assert (got["sends"], got["items"], got["streams"]) == (steps, 32 * steps, 32 * steps), got
    assert got["deferred"] == got["waits"] == 0, got
    assert seen["stream_items"] == steps and seen["stream_item"] == 0, seen
    # a gap an item from each stream's second on; an item's wait for its flush
    assert got["sent"] == 32 * (steps - 1) and got["wake"] == 32 * steps, got


def test_items_of_one_stream_in_one_message_keep_their_order(rows):
    """Three steps pushed and ONE flush: a message may carry several items
    of a stream; they keep their indexes."""
    actor, before = rows
    gens = _open(actor, ("a", "b"))
    for k in range(3):
        ray_tpu.get(actor.step.remote(k, k == 2), timeout=30)
    ray_tpu.get(actor.end.remote(), timeout=30)
    for name, gen in gens.items():
        assert [ray_tpu.get(r, timeout=30) for r in gen] == [(name, k) for k in range(3)]
    got = _gained(actor, before)
    assert (got["sends"], got["items"], got["streams"]) == (1, 6, 2), got
    assert got["sent"] == 4, got  # a gap of 0 between items that left together


def test_a_lagging_consumer_holds_the_rest_in_the_sink(monkeypatch):
    """(b) a window of 4 and a consumer that has not asked yet: four items
    at the head, six held back and counted; they arrive as the acks open
    the window, with no further push."""
    monkeypatch.setenv("RAY_TPU_STREAMING_BACKPRESSURE_ITEMS", "4")
    # (``init`` reads it into this process's config too: put that back after)
    monkeypatch.setattr(GLOBAL_CONFIG, "streaming_backpressure_items", int("4"))
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        actor = Rows.remote()
        before = ray_tpu.get(actor.totals.remote(), timeout=60)
        head = get_ctx().head
        (gen,) = _open(actor, ("slow",)).values()
        for k in range(10):
            ray_tpu.get(actor.step.remote(k), timeout=30)
        time.sleep(0.3)
        with head.lock:
            assert sorted(head.streams[gen._task_id]["items"]) == [0, 1, 2, 3]
        got = _gained(actor, before)
        assert (got["items"], got["deferred"], got["waits"]) == (4, 6, 0), got
        it, out = iter(gen), []
        for _ in range(10):
            out.append(ray_tpu.get(next(it), timeout=30))
            with head.lock:
                held = head.streams[gen._task_id]
                assert len([i for i in held["items"] if i >= held["next"]]) <= 4
        assert out == [("slow", k) for k in range(10)]
        ray_tpu.get(actor.end.remote(), timeout=30)
        assert list(it) == []
        got = _gained(actor, before)
        assert (got["items"], got["deferred"], got["waits"]) == (10, 6, 6), got
        assert got["wait_s"] > 0.2, got  # they lay there while nobody asked
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("flush", [True, False], ids=["flushed", "left_in_the_outbox"])
def test_the_end_cannot_overtake_the_last_batch(rows, flush):
    """(c) a stream that ends in the breath that pushed its last items:
    the count the head is told equals the items delivered."""
    actor, _ = rows
    head = get_ctx().head
    for n in (1, 5, 16, 40):  # 40: past the window, the end waits for acks
        gen = actor.burst.options(num_returns="streaming").remote(n, flush)
        assert list(gen.values(timeout=30)) == list(range(n))
        with head.lock:
            assert head.streams[gen._task_id]["count"] == n


def test_an_error_comes_after_what_was_pushed(rows):
    """(d) the body raises with items still in the outbox: the consumer
    sees them all, then the error, as on the per-item path."""
    actor, _ = rows
    gens = _open(actor, ("x",))
    for k in range(3):
        ray_tpu.get(actor.step.remote(k, False), timeout=30)  # pushed, never flushed
    ray_tpu.get(actor.end.remote("boom"), timeout=30)
    it, out = iter(gens["x"]), []
    with pytest.raises(RayTaskError, match="row x failed"):
        for ref in it:
            out.append(ray_tpu.get(ref, timeout=30))
    assert out == [("x", k) for k in range(3)]


@pytest.mark.parametrize("how", ["dispose", "cancel"])
def test_a_consumer_that_walks_away_leaves_nothing(rows, how):
    """(d) ``close`` / ``ray_tpu.cancel`` with items in the outbox: the
    body's wait ends, what was not sent is dropped, what was stored is
    released, and the object audit finds nothing."""
    actor, _ = rows
    head = get_ctx().head
    gens = _open(actor, ("gone", "stays"))
    for k in range(4):
        ray_tpu.get(actor.step.remote(k), timeout=30)
    gone, stays = gens["gone"], gens["stays"]
    first = next(iter(gone))
    assert ray_tpu.get(first, timeout=30) == ("gone", 0)
    del first
    ray_tpu.get(actor.step.remote(4, False), timeout=30)  # in the outbox
    if how == "dispose":
        gone.close()
    else:
        ray_tpu.cancel(gone._completion_ref)
    deadline = time.time() + 20
    while "gone" in ray_tpu.get(actor.open_rows.remote(), timeout=30):
        assert time.time() < deadline, "the cancelled body still waits"
        time.sleep(0.05)
    ray_tpu.get(actor.step.remote(5), timeout=30)  # the other stream goes on
    ray_tpu.get(actor.end.remote(), timeout=30)
    assert list(stays.values(timeout=30)) == [("stays", k) for k in range(6)]
    if how == "cancel":
        with pytest.raises(ray_tpu.exceptions.RayError):
            [ray_tpu.get(ref, timeout=30) for ref in gone]  # (a scope of its own)
        gone.close()
    oids = _item_ids(gone, 8) + _item_ids(stays, 8)
    del gone, stays, gens
    assert _none_left(head, oids) == []
    assert get_ctx().call("object_audit", timeout=2.0)["findings"] == []


def test_a_body_that_does_not_adopt_sends_item_by_item(rows):
    """(e) the per-item path is what it was: one ``stream_item`` an item,
    no batch counted; and both paths run side by side in one process."""
    actor, before = rows
    seen = _count_messages(get_ctx().head)
    plain = _open(actor, ("p0", "p1"), adopt=False)
    batched = _open(actor, ("b0",))
    for k in range(5):
        ray_tpu.get(actor.step.remote(k), timeout=30)
    ray_tpu.get(actor.end.remote(), timeout=30)
    for name, gen in {**plain, **batched}.items():
        assert list(gen.values(timeout=30)) == [(name, k) for k in range(5)]
    got = _gained(actor, before)
    assert seen["stream_item"] == 10 and seen["stream_items"] == 5, seen
    assert (got["sends"], got["items"], got["streams"]) == (5, 5, 5), got
    assert got["sent"] == 3 * 4, got  # every stream's gaps, whichever way they went


def test_a_plain_call_adopts_nothing(ray_start_regular):
    """``adopt`` outside a streaming task's drive: None, here and in a
    task, and a thread that drove a stream is clean afterwards."""
    assert stream_sink.adopt() is None

    @ray_tpu.remote
    def probe():
        return stream_sink.adopt() is None

    @ray_tpu.remote(num_returns="streaming")
    def one():
        yield stream_sink.adopt() is not None

    assert ray_tpu.get(probe.remote(), timeout=30)
    assert list(one.remote().values(timeout=30)) == [True]
    assert all(ray_tpu.get([probe.remote() for _ in range(8)], timeout=30))


class _Ctx:
    """A worker's connection, as far as a flush needs it."""

    def __init__(self):
        self.sent = []

    def store_value(self, sv):
        return ("inline", sv.to_bytes(), False)

    def send_raw(self, msg):
        self.sent.append(msg)


class _State:
    def __init__(self):
        self.ctx, self.stream_lock = _Ctx(), threading.Lock()
        self.cancel_requested, self.outbox = set(), None


class _Stream:
    acked, cond, sink = 0, None, None


def test_pushers_flushers_and_acks_race_without_loss():
    """The outbox under more threads than cores and a short switch
    interval: six threads push to their own sinks, three flush, one acks
    and wakes the sender (so the window of 16 holds items back all the time).  Every sink's
    items leave exactly once, in push order, under consecutive indexes."""
    import sys

    from ray_tpu._private import serialization as ser

    state = _State()
    outbox = stream_sink.Outbox(state)
    sinks = [stream_sink.Sink(outbox, bytes([i]) * 16, _Stream(), 0) for i in range(12)]
    n_items, stop = 400, threading.Event()

    def pusher(mine):
        for k in range(n_items):
            for sink in mine:
                sink.push((sink.task_id[0], k))

    def flusher():
        while not stop.is_set():
            outbox.flush()

    def acker():
        while not stop.is_set():
            for sink in sinks:
                with state.stream_lock:
                    sink.stream.acked = sink.next  # the consumer took what was sent
            outbox.flush_soon()  # as the recv thread does: the sender's

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pushers = [threading.Thread(target=pusher, args=(sinks[i::6],)) for i in range(6)]
        others = [threading.Thread(target=flusher) for _ in range(3)]
        others.append(threading.Thread(target=acker))
        for t in pushers + others:
            t.start()
        for t in pushers:
            t.join(timeout=60)
        deadline = time.time() + 30
        while any(s.next < n_items for s in sinks) and time.time() < deadline:
            time.sleep(0.01)
        stop.set()
        for t in others:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in pushers + others)
    finally:
        sys.setswitchinterval(old)
    got = collections.defaultdict(list)
    for kind, entries in state.ctx.sent:
        assert kind == "stream_items"
        for e in entries:
            value = ser.deserialize_value(ser.SerializedValue.from_bytes(e["locator"][1]))
            got[e["task_id"]].append((e["index"], value))
    for sink in sinks:
        assert got[sink.task_id] == [(k, (sink.task_id[0], k)) for k in range(n_items)]
        assert not sink.held and sink.close(drain=True) == n_items
    assert not outbox.items and not outbox.waiting
