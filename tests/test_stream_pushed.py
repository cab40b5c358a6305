"""The consumer half of the streaming path: ``ObjectRefGenerator.values()``
SUBSCRIBES once and is pushed to.  What a producer sent as one
``stream_items`` goes on to a consumer connection as ONE ``stream_push``,
and what that consumer's iterators took comes back as ONE
``stream_consumed`` and goes on as ONE ``stream_ack`` a producing worker.

``tests/test_stream_batched.py`` pins the producer half; its ``Rows`` actor
stands for an engine here too.  ``Reader`` stands for the HTTP proxy: a
worker of its own, a thread a stream.
"""

import collections
import queue
import threading
import time

import numpy as np
import pytest
from test_stream_batched import Rows, _gained, _item_ids, _none_left, _open

import ray_tpu
from ray_tpu._private import serialization as ser
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.runtime import ObjectRefGenerator, _Inbox, get_ctx
from ray_tpu.exceptions import RayTaskError


@ray_tpu.remote(max_concurrency=48)
class Reader:
    """A consumer in a worker of its own, as the HTTP proxy is: it opens
    its streams itself and reads each with ``values()`` on a thread."""

    def __init__(self):
        self.got = collections.defaultdict(list)
        self.gates = {}
        self.gens = {}
        self.errors = {}
        self.threads = []

    def open(self, rows, names, gated=(), **kw):
        for name in names:
            self.gens[name] = rows.row.options(num_returns="streaming").remote(name, **kw)
            if name in gated:
                self.gates[name] = queue.SimpleQueue()
            t = threading.Thread(target=self._read, args=(name,), daemon=True)
            self.threads.append(t)
            t.start()
        return [g._task_id for g in self.gens.values()]

    def _read(self, name):
        gate = self.gates.get(name)
        try:
            it = self.gens[name].values(timeout=30)
            while True:
                if gate is not None:
                    gate.get()  # the consumer takes an item when it is let
                self.got[name].append(next(it))
        except StopIteration:
            pass
        except BaseException as e:  # noqa: BLE001
            self.errors[name] = repr(e)

    def let(self, name, n=1):
        for _ in range(n):
            self.gates[name].put(None)

    def taken(self):
        return {name: len(self.got[name]) for name in self.gens}

    def results(self, timeout=30):
        for t in self.threads:
            t.join(timeout)
        return dict(self.got), dict(self.errors)


def _wait(cond, what, seconds=30.0):
    deadline = time.time() + seconds
    while not cond():
        assert time.time() < deadline, what
        time.sleep(0.01)


def _count_sends(monkeypatch) -> collections.Counter:
    """Count what the head (this process) sends from here on, by kind."""
    sent, send = collections.Counter(), ser.conn_send

    def counting(conn, msg):
        sent[msg[0]] += 1
        if msg[0] in ("stream_push", "stream_ack"):
            sent[msg[0] + ".entries"] += len(msg[1])
        return send(conn, msg)

    monkeypatch.setattr(ser, "conn_send", counting)
    return sent


def _count_received(head) -> collections.Counter:
    """Count what the workers send the head from here on: messages by kind,
    requests by method too."""
    seen, orig = collections.Counter(), head._handle_worker_msg

    def counting(conn, wh, remote, msg):
        seen[msg[0]] += 1
        if msg[0] == "req":
            seen[msg[2]] += 1
        return orig(conn, wh, remote, msg)

    head._handle_worker_msg = counting
    return seen


@pytest.fixture
def rows(ray_start_regular):
    actor = Rows.remote()
    return actor, ray_tpu.get(actor.totals.remote(), timeout=60)


@pytest.mark.parametrize("n", [3, 16], ids=["three_streams", "sixteen_streams"])
def test_a_step_is_one_push_and_its_acks_one_message(rows, monkeypatch, n):
    """N streams of one producer read by N threads of one consumer
    process: a step is ONE ``stream_items`` in, ONE ``stream_push`` out to
    the consumer's connection, and the N items its iterators took come back
    gathered (``stream_consumed``) and go on as ``stream_ack`` messages that
    carry several streams each: counted at the head and by the producer's
    own ``ack`` counters.  Every item arrives exactly once and in order."""
    actor, before = rows
    head = get_ctx().head
    reader = Reader.remote()
    names = list(range(n))
    ray_tpu.get(reader.open.remote(actor, names), timeout=60)
    _wait(lambda: len(ray_tpu.get(actor.open_rows.remote(), timeout=30)) == n,
          "the rows never opened")
    time.sleep(0.2)  # every reader is parked on its inbox now
    sent, asked = _count_sends(monkeypatch), _count_received(head)
    steps = 12
    for k in range(steps):
        assert ray_tpu.get(actor.step.remote(k), timeout=30) == n
        _wait(lambda: set(ray_tpu.get(reader.taken.remote(), timeout=30).values()) == {k + 1},
              "the readers never took the step's items")
        time.sleep(0.02)  # and its acks are out
    assert asked["stream_items"] == steps, asked
    assert sent["stream_push"] == steps and sent["stream_push.entries"] == n * steps, sent
    # nobody asks for an item, and the acks come gathered: a message a step
    # where the N threads take their items within the flusher's patience
    assert asked["stream_next"] == 0 and asked["stream_subscribe"] == 0, asked
    assert steps <= asked["stream_consumed"] <= 3 * steps, asked
    assert sent["stream_ack"] == asked["stream_consumed"], (sent, asked)
    assert sent["stream_ack.entries"] == n * steps, sent
    got = _gained(actor, before)
    assert got["ack_items"] == n * steps and got["ack_streams"] == n * steps, got
    assert got["ack_messages"] == sent["stream_ack"], (got, sent)
    assert got["ack_streams"] / got["ack_messages"] >= n / 3, got
    assert got["deferred"] == got["waits"] == 0, got
    ray_tpu.get(actor.end.remote(), timeout=30)
    results, errors = ray_tpu.get(reader.results.remote(), timeout=60)
    assert errors == {}
    assert results == {name: [(name, k) for k in range(steps)] for name in names}


def test_acks_of_two_producers_are_one_message_each(ray_start_regular, monkeypatch):
    """One consumer process reading streams of TWO producing workers: its
    one ``stream_consumed`` goes on as one ``stream_ack`` a producer."""
    a, b = Rows.remote(), Rows.remote()
    reader = Reader.remote()
    ray_tpu.get(reader.open.remote(a, ["a0", "a1", "a2"]), timeout=60)
    ray_tpu.get(reader.open.remote(b, ["b0", "b1", "b2"]), timeout=60)
    for actor in (a, b):
        _wait(lambda: len(ray_tpu.get(actor.open_rows.remote(), timeout=30)) == 3,
              "the rows never opened")
    before = [ray_tpu.get(x.totals.remote(), timeout=30) for x in (a, b)]
    for k in range(6):
        ray_tpu.get([a.step.remote(k), b.step.remote(k)], timeout=30)
        _wait(lambda: set(ray_tpu.get(reader.taken.remote(), timeout=30).values()) == {k + 1},
              "the readers never took the step's items")
    time.sleep(0.1)
    for actor, was in zip((a, b), before):
        got = _gained(actor, was)
        assert got["ack_items"] == got["ack_streams"] == 18, got
        assert got["ack_messages"] <= 12, got  # (6 where both pushes fall in one gathering)
    ray_tpu.get([a.end.remote(), b.end.remote()], timeout=30)
    results, errors = ray_tpu.get(reader.results.remote(), timeout=60)
    assert errors == {} and all(len(v) == 6 for v in results.values()), (results, errors)


def test_a_late_subscriber_catches_up_in_one_push(ray_start_regular):
    """A subscription that arrives after the items did brings them all in
    ONE entry, the end with it; nothing is asked for."""
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    ctx = get_ctx()
    head, pushes, calls = ctx.head, [], []
    push, call = ctx._on_stream_push, ctx.call

    def counting_push(entries):
        pushes.extend(entries)
        return push(entries)

    def counting_call(method, **payload):
        calls.append(method)
        return call(method, **payload)

    ctx._on_stream_push, ctx.call = counting_push, counting_call
    try:
        g = gen.remote(12)

        def over():
            with head.lock:
                st = head.streams.get(g._task_id)
                return st is not None and st["count"] == 12
        _wait(over, "the stream never ended")  # all twelve have arrived (window 16)
        assert list(g.values(timeout=30)) == list(range(12))
    finally:
        del ctx._on_stream_push, ctx.call
    assert [(start, len(items), end) for _tid, start, items, end in pushes] == [(0, 12, (12, None))]
    assert "stream_next" not in calls and calls.count("stream_subscribe") == 1, calls


def test_a_subscription_after_the_end_or_the_disposal_ends_at_once(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def nothing():
        return
        yield

    @ray_tpu.remote(num_returns="streaming")
    def three():
        yield from range(3)

    head = get_ctx().head
    g = nothing.remote()
    ray_tpu.get(g._completion_ref, timeout=30)  # over before anybody subscribed
    assert list(g.values(timeout=30)) == []
    # a disposed stream answers a subscription with its end, whoever asks
    g = three.remote()
    g.close()
    got = []
    head.stream_subscribe_local(got.extend, g._task_id, 0)
    assert got == [(g._task_id, 0, [], (0, None))]
    assert ("fn", got.extend) not in head._stream_subs
    assert list(g.values(timeout=30)) == []


@pytest.mark.parametrize("consumer", ["driver", "worker"])
def test_a_producers_exception_comes_after_its_items(rows, consumer):
    """What was yielded before the failure is delivered, then the
    producer's own exception is raised: in the head's own process (a direct
    call delivers) and over a worker's connection."""
    actor, _ = rows
    if consumer == "driver":
        gens = _open(actor, ("x",))
    else:
        reader = Reader.remote()
        ray_tpu.get(reader.open.remote(actor, ["x"]), timeout=60)
        _wait(lambda: ray_tpu.get(actor.open_rows.remote(), timeout=30) == ["x"],
              "the row never opened")
    for k in range(3):
        ray_tpu.get(actor.step.remote(k), timeout=30)
    ray_tpu.get(actor.end.remote("boom"), timeout=30)
    if consumer == "driver":
        out = []
        with pytest.raises(RayTaskError, match="row x failed"):
            for v in gens["x"].values(timeout=30):
                out.append(v)
    else:
        results, errors = ray_tpu.get(reader.results.remote(), timeout=60)
        out = results["x"]
        assert "row x failed" in errors["x"], errors
    assert out == [("x", k) for k in range(3)]


def test_a_slow_consumer_stalls_its_own_stream_only(monkeypatch):
    """A window of 4, a consumer that takes nothing of one stream and
    everything of another: the slow stream's producer stops four items
    ahead of what was TAKEN (pushed is not taken), the other runs on; each
    item let through opens the window by one."""
    monkeypatch.setenv("RAY_TPU_STREAMING_BACKPRESSURE_ITEMS", "4")
    monkeypatch.setattr(GLOBAL_CONFIG, "streaming_backpressure_items", int("4"))
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        actor = Rows.remote()
        before = ray_tpu.get(actor.totals.remote(), timeout=60)
        reader = Reader.remote()
        slow_id, _ = ray_tpu.get(
            reader.open.remote(actor, ["slow", "fast"], gated=("slow",)), timeout=60)
        _wait(lambda: len(ray_tpu.get(actor.open_rows.remote(), timeout=30)) == 2,
              "the rows never opened")
        head = get_ctx().head
        for k in range(10):
            ray_tpu.get(actor.step.remote(k), timeout=30)
            _wait(lambda: ray_tpu.get(reader.taken.remote(), timeout=30)["fast"] == k + 1,
                  "the fast stream stalled behind the slow one")
            if k == 0:  # the slow one subscribes, takes its first item and no more
                ray_tpu.get(reader.let.remote("slow"), timeout=30)
                _wait(lambda: ray_tpu.get(reader.taken.remote(), timeout=30)["slow"] == 1,
                      "the slow stream's first item never came")
        got = _gained(actor, before)
        # ten of the fast stream left and five of the slow (one taken, four
        # pushed and lying in the consumer's inbox): five held back
        assert (got["items"], got["deferred"]) == (15, 5), got
        with head.lock:
            st = head.streams[slow_id]
            assert (st["next"], st["acked"], len(st["items"])) == (5, 1, 5), st
        for k in range(1, 10):
            ray_tpu.get(reader.let.remote("slow"), timeout=30)
            _wait(lambda: ray_tpu.get(reader.taken.remote(), timeout=30)["slow"] == k + 1,
                  "the slow stream's item never came")
            with head.lock:
                st = head.streams[slow_id]
                # never more than the window ahead of what was taken
                assert len(st["items"]) - (k + 1) <= 4, (k, st)
                assert st["acked"] <= k + 1
        _wait(lambda: _gained(actor, before)["items"] == 20, "the held items never left")
        ray_tpu.get(actor.end.remote(), timeout=30)
        ray_tpu.get(reader.let.remote("slow"), timeout=30)  # it meets the end
        results, errors = ray_tpu.get(reader.results.remote(), timeout=60)
        assert errors == {}
        assert results == {n: [(n, k) for k in range(10)] for n in ("slow", "fast")}
    finally:
        ray_tpu.shutdown()


def test_closing_a_pushed_stream_leaves_nothing(rows):
    """``close`` mid-stream with items pushed and not taken, one of them
    too large to ride inline: the producer's body ends, what the head still
    held and what the consumer was sent and never took is freed."""
    actor, _ = rows
    head = get_ctx().head
    big = np.arange(400_000, dtype=np.int64)  # 3.2 MB: a reference, fetched

    @ray_tpu.remote(num_returns="streaming")
    def mixed(n):
        for i in range(n):
            yield big if i == 1 else i

    whole = list(mixed.remote(3).values(timeout=30))
    assert whole[0] == 0 and whole[2] == 2 and int(whole[1][-1]) == 399_999
    del whole
    g = mixed.remote(4)
    it = g.values(timeout=30)
    assert next(it) == 0
    gens = _open(actor, ("gone", "stays"))
    for k in range(4):
        ray_tpu.get(actor.step.remote(k), timeout=30)
    gone, stays = gens["gone"], gens["stays"]
    taken = gone.values(timeout=30)
    assert next(taken) == ("gone", 0)
    time.sleep(0.2)  # the other three are pushed and lie in its inbox
    gone.close()
    g.close()
    _wait(lambda: "gone" not in ray_tpu.get(actor.open_rows.remote(), timeout=30),
          "the cancelled body still waits")
    assert list(taken) == [] and list(it) == []
    ray_tpu.get(actor.step.remote(4), timeout=30)  # the other stream goes on
    ray_tpu.get(actor.end.remote(), timeout=30)
    assert list(stays.values(timeout=30)) == [("stays", k) for k in range(5)]
    oids = _item_ids(gone, 8) + _item_ids(stays, 8) + _item_ids(g, 4)
    with head.lock:
        assert gone._task_id not in head.streams and not head._stream_subs.get(
            ("fn", get_ctx()._on_stream_push), set()) & {gone._task_id, g._task_id}
    assert gone._task_id not in get_ctx()._stream_inboxes
    del gone, stays, gens, g, it, taken
    assert _none_left(head, oids) == []
    assert get_ctx().call("object_audit", timeout=2.0)["findings"] == []


def test_a_consumer_process_that_dies_drops_its_subscriptions(rows):
    """The reading worker is killed mid-stream: the head disposes every
    stream it was pushed, their producers' bodies end, nothing is held."""
    actor, _ = rows
    head = get_ctx().head
    reader = Reader.remote()
    task_ids = ray_tpu.get(reader.open.remote(actor, ["r0", "r1", "r2"]), timeout=60)
    _wait(lambda: len(ray_tpu.get(actor.open_rows.remote(), timeout=30)) == 3,
          "the rows never opened")
    for k in range(3):
        ray_tpu.get(actor.step.remote(k), timeout=30)
    _wait(lambda: set(ray_tpu.get(reader.taken.remote(), timeout=30).values()) == {3},
          "the reader never took its items")
    with head.lock:
        (sink,) = [s for s, ids in head._stream_subs.items() if ids == set(task_ids)]
    assert sink[0] == "conn"
    ray_tpu.kill(reader)
    _wait(lambda: ray_tpu.get(actor.open_rows.remote(), timeout=30) == [],
          "the producers' bodies still wait for a dead consumer")
    with head.lock:
        assert sink not in head._stream_subs
        assert not any(t in head.streams for t in task_ids)
        assert all(t in head._disposed_streams for t in task_ids)
    # what a producer still sends for them is freed on arrival
    oids = [o for t in task_ids for o in _item_ids(type("G", (), {"_task_id": t}), 6)]
    assert _none_left(head, oids) == []


def test_parked_consumers_are_woken_in_the_pushs_order(rows):
    """Eight threads parked on eight streams: a step's push wakes them one
    after another in ITS order (the producer's rows), the same every step:
    each takes its entry only when the one before it has, so a stream's
    place in a step's burst does not move from step to step."""
    actor, _ = rows
    names = list("abcdefgh")
    gens = _open(actor, names)
    got = collections.defaultdict(list)

    def read(name):
        for item in gens[name].values(timeout=30):
            got[name].append(item)

    threads = [threading.Thread(target=read, args=(n,), daemon=True) for n in names]
    for t in threads:
        t.start()
    ctx = get_ctx()
    head, pushed, taken, steps = ctx.head, [], [], 15

    def parked():
        inboxes = list(ctx._stream_inboxes.values())
        return len(inboxes) >= len(names) and all(i.waiting for i in inboxes)

    _wait(parked, "the readers never parked")
    push, took = head._push_stream, ctx._stream_took

    def pushing(task_id, st):  # (under the head's lock, in the message's order)
        pushed.append(task_id)
        return push(task_id, st)

    def taking(task_id, *rest):  # (the iterator's next statement after its take)
        taken.append(task_id)
        return took(task_id, *rest)

    head._push_stream, ctx._stream_took = pushing, taking
    try:
        for k in range(steps):
            ray_tpu.get(actor.step.remote(k), timeout=30)
            _wait(lambda: len(taken) == (k + 1) * len(names), "a step's items never came")
            _wait(parked, "the readers never parked again")
    finally:
        del head._push_stream, ctx._stream_took
    assert taken == pushed and len(pushed) == steps * len(names)
    first = pushed[:len(names)]
    assert all(pushed[i:i + len(names)] == first for i in range(0, len(pushed), len(names)))
    ray_tpu.get(actor.end.remote(), timeout=30)
    for t in threads:
        t.join(30)
    assert got == {n: [(n, k) for k in range(steps)] for n in names}


def test_a_retired_inbox_passes_the_turn_on():
    """A consumer that is gone (closed, collected, its stream over) while a
    push's chain runs through it: whoever finds the entry passes the turn
    to the next, so the consumers behind it are still woken."""
    a, b, c = _Inbox(), _Inbox(), _Inbox()
    last = (c, (0, ["c0"], None, True, None))
    mid = (b, (0, ["b0"], None, True, last))
    b.retire()  # retired before the turn reaches it
    a.put((0, ["a0"], None, True, mid))
    entry = a.q.get_nowait()
    entry[4][0].put(entry[4][1])  # what an iterator does as it takes its entry
    assert b.q.empty() and c.q.get_nowait()[1] == ["c0"]
    # retired with the turn already in it: the retiring thread passes it on
    d, e = _Inbox(), _Inbox()
    d.put((0, ["d0"], None, True, (e, (0, ["e0"], None, True, None))))
    d.retire()
    assert d.q.empty() and e.q.get_nowait()[1] == ["e0"]


# -- the generator alone: entries filed in order, whatever order they come in --


class _StubCtx:
    """What a pushed generator needs of its context."""

    closed = False

    def __init__(self):
        self.subscribed, self.took, self.inboxes = [], [], {}
        self._stream_inboxes = self.inboxes

    def _stream_subscribe(self, task_id, index, inbox):
        self.subscribed.append((task_id, index))
        self.inboxes[task_id] = inbox

    def _stream_took(self, task_id, consumed, delivered, woke):
        self.took.append((consumed, list(delivered), woke))

    def _materialize(self, _oid, locator):
        return ser.deserialize_value(ser.SerializedValue.from_bytes(locator[1]))

    def _flush_stream_acks(self):
        pass

    def call(self, method, **payload):
        pass

    def enqueue_gc(self, kind, payload):
        pass


def _inline(*values):
    return [ser.serialize(v).to_bytes() for v in values]


def test_entries_that_overtake_each_other_are_put_in_order():
    """Two threads of the head may send a stream's entries at once: each
    says where its items start, and the iterator yields them in order, the
    end only after the last; an item is acked when taken, the gaps the
    consumer reported ride that ack, the last ones an ack of their own."""
    ctx = _StubCtx()
    g = ObjectRefGenerator(b"t" * 16, None, ctx)
    it = g.values(timeout=5)
    # (nothing is subscribed before the first item is asked for)
    got, feeder = [], queue.SimpleQueue()

    def feed():
        inbox = None
        while inbox is None:
            inbox = ctx.inboxes.get(b"t" * 16)
        for entry in iter(feeder.get, None):
            inbox.q.put(entry)

    t = threading.Thread(target=feed, daemon=True)
    t.start()
    feeder.put((3, _inline(3, 4), (6, None), False, None))  # overtook the two before it
    feeder.put((2, _inline(2), None, False, None))
    feeder.put((0, _inline(0, 1), None, True, None))
    for v in it:
        got.append(v)
        if v == 1:
            g.report_delivered([0.5])
        if v == 4:
            feeder.put((5, _inline(5), None, False, None))
        if v == 5:
            g.report_delivered([0.25, 0.125])
    feeder.put(None)
    t.join(10)
    assert got == [0, 1, 2, 3, 4, 5] and g._done
    assert ctx.subscribed == [(b"t" * 16, 0)]
    assert [c for c, _d, _w in ctx.took] == [1, 2, 3, 4, 5, 6, 6]
    assert [d for _c, d, _w in ctx.took] == [[], [], [0.5], [], [], [], [0.25, 0.125]]
    assert [w for _c, _d, w in ctx.took] == [True] + [False] * 6
    with pytest.raises(StopIteration):
        next(g)  # over, whichever way it is read


def test_a_stream_read_by_value_is_not_read_by_reference(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    # references first, values for the rest: the subscription starts there
    g = gen.remote(6)
    assert [ray_tpu.get(next(g), timeout=30) for _ in range(2)] == [0, 1]
    it = g.values(timeout=30)
    assert next(it) == 2
    with pytest.raises(RuntimeError, match="read by values"):
        next(g)
    assert list(it) == [3, 4, 5]
