"""The batched producer path of a streaming task: a body that holds MANY
streams' items at one moment (an LLM engine: every live row's token, once
a step) hands them to one sender, and they leave the process as ONE
``stream_items`` message that the head takes under one take of its lock.

The per-item path (``worker_main._stream_results_inner``) gives every
stream a handler thread that serializes, stores and sends each item it is
yielded: right for a generator that blocks between its items, and one
thread wake-up, one message, one take of the head's lock and one
``notify_all`` an item.  A body takes THIS path by adopting its stream's
sink; nothing else selects it::

    sink = stream_sink.adopt()    # None: this thread drives no stream
    sink.push(item, t_emit)       # any thread, never blocks
    sink.flush_soon()             # the worker's ONE sender thread sends the whole
                                  # outbox, every stream's items (``flush``: here, now)
    return                        # the generator ends the stream as ever

While ``_stream_results_inner`` drives a generator it publishes the
stream's record on its thread (``drive``), where ``adopt`` finds it.  The
body's generator then yields nothing more, and ends the stream by
returning or raising exactly as on the per-item path: the worker drains
what the sink still holds (``Sink.close``) before the completion leaves, so
the end cannot overtake the last batch.

The window is per stream, as on the per-item path: at most
``streaming_backpressure_items`` items un-acked at the head.  What exceeds
it stays in the sink and rides the flush that the ack asks the sender for
when it opens the window (``worker_main._on_stream_ack``), or the next one.  An item
keeps the index and object id the per-item path would have given it.

``adopt`` goes by the THREAD, not by the generator: a streaming body that
itself runs another adopting body on its thread (a wrapper that iterates
``LLMDeployment.__call__`` in-process to detokenize) would have the inner
body's items pushed into ITS stream and be yielded none.  Such a wrapper
runs the inner body on a thread of its own, or calls a non-streaming method.

Stations (``_private.stream_stats``): ``wake`` is an item's wait from its
push to the flush that picks it up (with ``flush_soon``: the sender thread's
wake-up); ``sent`` is observed per stream at
``send_raw``'s return of the message that carried the item (a gap of 0
between one stream's items in one message); ``backpressure`` counts an item
the window held back and how long; ``batch`` counts the messages.  The
flush is the span ``core.stream.batch`` (``n``, ``streams``).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from itertools import islice
from typing import Callable, Optional

from ray_tpu._private import events
from ray_tpu._private import serialization as ser
from ray_tpu._private import stream_stats
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.log_util import warn_throttled
from ray_tpu.util import tracing

#: the stream ``_stream_results_inner`` drives on this thread:
#: ``.drive = (state, task_id, stream, items_sent)``.  Imported by name
#: only: ``worker_main`` may run as ``__main__`` and holds no such state.
_driving = threading.local()


def _window() -> int:
    """Items of one stream that may be un-acked at the head: the per-item
    path's window (``worker_main._stream_results_inner``)."""
    return max(1, GLOBAL_CONFIG.streaming_backpressure_items)


def entry(ctx, task_id: bytes, idx: int, sv) -> dict:
    """Store item ``idx`` of a stream, serialized, and describe it to the
    head: what a ``stream_item`` message carries, and a ``stream_items``
    message a list of.  Both producer paths make their items here."""
    locator = ctx.store_value(sv)
    if locator[0] == "shm":
        events.emit(
            "core.object.put",
            size=locator[1].total_size,
            node=locator[1].node,
            seg=locator[1].name,
        )
    return {
        "task_id": task_id,
        "index": idx,
        "obj_id": ObjectID.for_task_return(TaskID(task_id), 1 + idx).binary(),
        "locator": locator,
    }


class Outbox:
    """A worker's one outbox (``WorkerState.outbox``, made at the first
    adoption): what its sinks were pushed, and the one sender."""

    def __init__(self, state):
        self.state = state
        # pushed and not yet picked up: (sink, item, t_emit) in push order;
        # the lock is held for an append or a swap, no longer
        self.items: list = []
        self.lock = threading.Lock()
        # one flush at a time: indexes are given and messages leave in
        # order.  Everything of a sink but ``closed`` is touched under it.
        self.send = threading.Lock()
        # sinks holding items their window kept back, in the order they fell behind
        self.waiting: dict = {}
        # the ONE sender thread (``flush_soon``), started when first asked for
        self.sender: Optional[threading.Thread] = None
        self.wake = threading.Event()

    def flush(self) -> None:
        """Send everything pushed so far, every stream's items in ONE
        message, on this thread."""
        with self.send:
            try:
                self._flush_locked()
            except Exception as e:  # noqa: BLE001
                # the connection to the head is gone and the worker on its
                # way out: the caller is the sender or a stream's handler
                # thread, and neither may die of it
                warn_throttled("stream_sink.flush", e)

    def flush_soon(self) -> None:
        """Have the sender thread ``flush``.  Returns at once: the caller
        (an engine's loop between two launches, the recv thread at an ack)
        serializes nothing, takes no lock a send is made under and parks on
        no connection."""
        if self.sender is None:
            with self.lock:
                if self.sender is None:
                    self.sender = threading.Thread(
                        target=self._send_loop, name="stream-sender", daemon=True
                    )
                    self.sender.start()
        self.wake.set()

    def _send_loop(self) -> None:
        while True:
            self.wake.wait()
            self.wake.clear()
            self.flush()

    def _flush_locked(self) -> None:
        with self.lock:
            taken, self.items = self.items, []
        waiting = self.waiting
        if not taken and not waiting:
            return
        st = stream_stats.stations()
        now = time.perf_counter()
        sinks = dict.fromkeys(waiting)  # what fell behind goes first
        for sink, item, t_emit in taken:
            st.wake.observe(now - t_emit)
            sink.held.append([item, None])
            sinks[sink] = None
        # the window, per stream: how many of its items may leave now
        cap = _window()
        with self.state.stream_lock:
            room = {sink: cap - (sink.next - sink.stream.acked) for sink in sinks}
        going = []
        for sink in sinks:
            if sink.closed:
                sink.held.clear()
                waiting.pop(sink, None)
                continue
            n = min(len(sink.held), max(0, room[sink]))
            if n:
                going.append((sink, n))
            if n < len(sink.held):
                waiting[sink] = None
                fresh = [e for e in islice(sink.held, n, None) if e[1] is None]
                for entry in fresh:
                    entry[1] = now  # held back from here on
                st.deferred.inc(len(fresh))
            else:
                waiting.pop(sink, None)
        if not going:
            return
        n_items = sum(n for _, n in going)
        with tracing.annotate("core.stream.batch", n=n_items, streams=len(going)):
            entries = []
            went = [(sink, sink._take(n, entries, st, now)) for sink, n in going]
            if not entries:
                return
            self.state.ctx.send_raw(("stream_items", entries))
        now = time.perf_counter()
        went = [(sink, n) for sink, n in went if n]  # (0: its item would not serialize)
        for sink, n in went:
            if sink.t_sent is not None:  # a stream's first item has no gap
                st.sent.observe(now - sink.t_sent)
            for _ in range(n - 1):
                st.sent.observe(0.0)  # they left at one moment
            sink.t_sent = now
        st.sends.inc()
        st.items.inc(len(entries))
        st.streams.inc(len(went))


class Sink:
    """An adopted stream's end of the batched path."""

    __slots__ = ("outbox", "task_id", "stream", "held", "next", "t_sent",
                 "closed", "error", "_wake")

    def __init__(self, outbox: Outbox, task_id: bytes, stream, start: int):
        self.outbox = outbox
        self.task_id = task_id
        self.stream = stream
        self.held: deque = deque()  # picked up, not sent: [item, t_held]
        self.next = start           # the index the next item sent gets
        self.t_sent: Optional[float] = None  # the `sent` station's stamp
        self.closed = False         # ended or cancelled: what comes now is dropped
        self.error: Optional[BaseException] = None  # an item that would not serialize
        self._wake: Optional[Callable[[], None]] = None

    def push(self, item, t_emit: Optional[float] = None) -> None:
        """Queue one item of this stream for the next ``flush``.  Any
        thread; never blocks.  ``t_emit`` (``perf_counter``) is when the
        item was made, where the caller stamped that itself."""
        entry = (self, item, time.perf_counter() if t_emit is None else t_emit)
        outbox = self.outbox
        with outbox.lock:
            outbox.items.append(entry)

    def flush(self) -> None:
        """Send what EVERY sink of this worker was pushed so far, here."""
        self.outbox.flush()

    def flush_soon(self) -> None:
        """The same from the worker's one sender thread; returns at once."""
        self.outbox.flush_soon()

    @property
    def cancelled(self) -> bool:
        return self.task_id in self.outbox.state.cancel_requested

    def on_cancel(self, wake: Callable[[], None]) -> None:
        """``wake`` ends the body's wait when the task is cancelled (the
        consumer walked away) or an item would not serialize: it runs on
        the thread that finds out, or here at once if that came first."""
        self._wake = wake
        if self.closed or self.cancelled:
            wake()

    def abandon(self, error: Optional[BaseException] = None) -> None:
        """Drop what is not sent yet and end the body's wait."""
        self.closed = True
        if error is not None:
            self.error = error
        if self._wake is not None:
            self._wake()

    def _take(self, n: int, entries: list, st, now: float) -> int:
        """``Outbox._flush_locked``: serialize, store and list the first
        ``n`` items held.  Returns how many went."""
        state = self.outbox.state
        for i in range(n):
            item, t_held = self.held.popleft()
            try:
                sv = ser.serialize(item)
            except Exception as e:  # noqa: BLE001 - ends THIS stream, as on the per-item path
                self.held.clear()
                self.outbox.waiting.pop(self, None)
                self.abandon(e)
                return i
            entries.append(entry(state.ctx, self.task_id, self.next, sv))
            self.next += 1
            if t_held is not None:
                st.waits.inc()
                st.wait_s.inc(now - t_held)
        return n

    def close(self, drain: bool) -> int:
        """The body's generator has ended.  With ``drain``, everything
        pushed leaves first, waiting for the window where it must (the
        handler thread's wait, as on the per-item path); a cancelled stream
        drops what it holds.  Returns the count of items sent."""
        outbox, stream = self.outbox, self.stream
        stream_lock = outbox.state.stream_lock
        cap = _window()
        while drain and not self.cancelled and self.error is None:
            outbox.flush()
            if not self.held:
                break
            with stream_lock:
                if self.next - stream.acked >= cap:
                    if stream.cond is None:
                        stream.cond = threading.Condition(stream_lock)
                    stream.cond.wait(timeout=0.5)  # an ack notifies it
        self.closed = True
        self._wake = None  # (and what it holds: the body's request)
        with outbox.send:
            self.held.clear()
            outbox.waiting.pop(self, None)
            return self.next


def drive(state, task_id: bytes, stream, items_sent: Callable[[], int]) -> None:
    """``_stream_results_inner`` is about to run a generator on this
    thread: its body may adopt the stream's sink from here on."""
    _driving.drive = (state, task_id, stream, items_sent)


def drive_ended() -> None:
    _driving.drive = None


def adopt() -> Optional[Sink]:
    """Take over the sink of the stream this thread drives.  None where it
    drives none (a plain call of the same body, a generator run on another
    thread): the caller then yields its items as ever."""
    got = getattr(_driving, "drive", None)
    if got is None:
        return None
    state, task_id, stream, items_sent = got
    if stream.sink is None:
        with state.stream_lock:
            if state.outbox is None:
                state.outbox = Outbox(state)
        stream.sink = Sink(state.outbox, task_id, stream, items_sent())
    return stream.sink
