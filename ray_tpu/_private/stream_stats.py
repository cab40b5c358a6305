"""The streamed token's stations (OBSERVABILITY.md, "The streamed token's
stations"): the gap between two items of one stream, measured where the
item passes — never a clock compared across processes.

A delay that is the same for token n-1 and token n adds nothing to the
gap a client sees; only a delay that DIFFERS does.  So each station keeps,
per stream, when the previous item passed it (one ``perf_counter`` float of
its own process) and observes the gap; where the gap's 95th percentile
grows from one station to the next is where the tail is made.

* ``emit`` — the engine's loop thread at ``LLMEngine._emit``: this is
  ``llm_inter_token_latency_s`` (``llm.engine``), on the same boundaries.
* ``sent`` — the producing worker: ``send_raw`` of the message that carried
  the item returned.  On the per-item path that is the stream's own handler
  thread and a ``stream_item`` (``worker_main._stream_results_inner``); on
  the batched path (a body that adopted its stream's sink:
  ``_private.stream_sink``) it is the flushing thread and ONE
  ``stream_items`` for every stream's items of a step, and items of one
  stream in one message read a gap of 0 between them.
* ``acked`` — the worker's recv loop, the item's ``stream_ack`` arrived:
  the consumer TOOK it, plus the two hops back.  A consumer that reads
  values (``ObjectRefGenerator.values``) is pushed to and acks what its
  iterators took, every stream of its process in ONE message
  (``BaseContext._flush_stream_acks``), which the head passes on as ONE
  ``stream_ack`` a producing worker; items of one stream acked together
  read a gap of 0 between them.  A consumer that reads references asks
  for each (``stream_next``), and the ask is the ack.
* ``written`` — the consumer's own report (``ObjectRefGenerator
  .report_delivered``; the HTTP proxy reports the gaps between chunks
  written and drained), carried by the ack of the next item taken (or the
  next ``stream_next``).

Two legs are durations inside one process: ``wake`` (a token's wait for
the thread that sends it: in ``req.stream`` for its handler thread,
``LLMEngine.stream_tokens``, or in the outbox until ``stream_sink.Outbox.flush``
picks it up) and ``head_hold`` (an item's stay in the head: from its arrival
to its push to the stream's subscriber, or to the ask that took it;
``hold_s`` on the ack).  All of it lands in the PRODUCING
worker's registry, where ``snapshot()`` reads it for
``LLMDeployment.stats()``.

The batched path counts itself under ``batch``: ``sends`` (``stream_items``
messages), ``items`` and ``streams`` (what they carried: ``items / sends``
is the rows a message carries, about the live rows of a step) and
``deferred`` (items a stream's ack window held back to a later message).
``backpressure`` keeps its meaning on both paths: an item that had to wait
for its stream's window, and how long.

The acks count themselves under ``ack``: ``messages`` (``stream_ack``
messages this worker received), ``streams`` (the acks they carried, one a
stream) and ``items`` (the items those said were taken).  ``streams /
messages`` near the live rows of a step says the consumer's acks come
gathered; near 1, that each stream's ack travels alone (a consumer that
asks item by item, or one whose streams do not move together).
"""

from __future__ import annotations

import threading

from ray_tpu.util.metrics import FINE_LATENCY_BOUNDS_S

#: raylint RL012 registry
METRIC_NAMES = (
    "core_stream_gap_s",
    "core_stream_leg_s",
    "core_stream_backpressure_waits",
    "core_stream_backpressure_wait_s",
    "core_stream_batch_sends",
    "core_stream_batch_items",
    "core_stream_batch_streams",
    "core_stream_batch_deferred",
    "core_stream_ack_messages",
    "core_stream_ack_streams",
    "core_stream_ack_items",
)

STATIONS = ("sent", "acked", "written")
LEGS = ("wake", "head_hold")
BATCH = ("sends", "items", "streams", "deferred")
ACK = ("messages", "streams", "items")


class _Stations:
    """The process's station series, each bound to its tag set once."""

    __slots__ = STATIONS + LEGS + BATCH + ("waits", "wait_s") + tuple(
        "ack_" + name for name in ACK)

    def __init__(self):
        from ray_tpu.util.metrics import Counter, Histogram

        gap = Histogram(
            "core_stream_gap_s",
            "gap between consecutive items of one stream, by the station "
            "they passed (first item excluded)",
            boundaries=FINE_LATENCY_BOUNDS_S, tag_keys=("station",),
        )
        leg = Histogram(
            "core_stream_leg_s",
            "time an item spent on one leg of the streaming path",
            boundaries=FINE_LATENCY_BOUNDS_S, tag_keys=("leg",),
        )
        for s in STATIONS:
            setattr(self, s, gap.bind({"station": s}))
        for name in LEGS:
            setattr(self, name, leg.bind({"leg": name}))
        self.waits = Counter(
            "core_stream_backpressure_waits",
            "times a stream's producer waited for its ack window",
        )
        self.wait_s = Counter(
            "core_stream_backpressure_wait_s",
            "seconds stream producers waited for their ack windows",
        )
        self.sends = Counter(
            "core_stream_batch_sends",
            "stream_items messages sent by the batched producer path",
        )
        self.items = Counter(
            "core_stream_batch_items", "items those messages carried",
        )
        self.streams = Counter(
            "core_stream_batch_streams",
            "streams those messages carried items of, summed over messages",
        )
        self.deferred = Counter(
            "core_stream_batch_deferred",
            "items a stream's ack window held back to a later message",
        )
        self.ack_messages = Counter(
            "core_stream_ack_messages",
            "stream_ack messages this producing worker received",
        )
        self.ack_streams = Counter(
            "core_stream_ack_streams",
            "acks those messages carried, one a stream, summed over messages",
        )
        self.ack_items = Counter(
            "core_stream_ack_items", "items those acks said the consumer took",
        )


_STATIONS = None
_STATIONS_LOCK = threading.Lock()


def stations() -> _Stations:
    global _STATIONS
    if _STATIONS is not None:
        return _STATIONS  # lock-free fast path: resolved once a stream
    with _STATIONS_LOCK:
        if _STATIONS is None:
            _STATIONS = _Stations()
    return _STATIONS


def snapshot(emit: list) -> dict:
    """``stats()["stream"]``: this process's cumulative bucket vectors on
    one ``bounds_s`` (``emit`` is the caller's: the engine owns it).  Reads
    the metric registry alone — no lock of the engine or the worker."""
    st = stations()
    out = {"bounds_s": list(FINE_LATENCY_BOUNDS_S), "emit": emit}
    for name in STATIONS + LEGS:
        out[name] = getattr(st, name).buckets()
    out["backpressure"] = {
        "waits": int(st.waits.value()),
        "wait_s": st.wait_s.value(),
    }
    out["batch"] = {name: int(getattr(st, name).value()) for name in BATCH}
    out["ack"] = {name: int(getattr(st, "ack_" + name).value()) for name in ACK}
    return out
