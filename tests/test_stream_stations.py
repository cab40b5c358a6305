"""The streamed token's stations (OBSERVABILITY.md, "The streamed token's
stations"): the gap between a stream's items is measured where the loop
emits it, where the producing worker sends it, where its ack comes back
and where the consumer says it wrote it; two legs (``wake``, ``head_hold``)
are durations inside one process.  All of it is read from the PRODUCING
worker's metric registry, as ``LLMDeployment.stats()["stream"]`` does.
"""

import json
import random
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import stream_stats
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.util.metrics import (
    FINE_LATENCY_BOUNDS_S,
    Histogram,
    percentiles_from_buckets,
)

STATION_KEYS = ("emit", "sent", "acked", "written", "wake", "head_hold")


@ray_tpu.remote
class Producer:
    """A streaming actor whose process's station vectors can be read."""

    def items(self, n, sleep_s=0.0):
        for i in range(n):
            if sleep_s:
                time.sleep(sleep_s)  # inside the handler thread
            yield i

    def totals(self):
        snap = stream_stats.snapshot(emit=[])
        out = {k: sum(snap[k]) for k in STATION_KEYS}
        out["backpressure"] = snap["backpressure"]
        # observations above 30 ms, by station
        lo = next(i for i, b in enumerate(snap["bounds_s"]) if b >= 0.030)
        out["slow"] = {k: sum(snap[k][lo + 1:]) for k in STATION_KEYS}
        return out


def _gained(actor, before):
    """The actor's totals minus ``before``, once its last acks are in."""
    deadline = time.time() + 10
    while True:
        now = ray_tpu.get(actor.totals.remote(), timeout=30)
        got = {k: now[k] - before[k] for k in STATION_KEYS}
        got["slow"] = {k: now["slow"][k] - before["slow"][k] for k in STATION_KEYS}
        got["waits"] = now["backpressure"]["waits"] - before["backpressure"]["waits"]
        got["wait_s"] = now["backpressure"]["wait_s"] - before["backpressure"]["wait_s"]
        if got["acked"] >= got["sent"] or time.time() > deadline:
            return got
        time.sleep(0.05)


#: a consumer reads references (an ask an item) or values (pushed, acks gathered)
READS = pytest.mark.parametrize("values", [False, True], ids=["by_reference", "by_value"])


def _consume(gen, report=True, sleep_every=0, sleep_s=0.0, values=False):
    """Drain a stream as a consumer that passes items on would: before each
    ask it reports the gap between the two items it 'wrote' last."""
    out, t_prev = [], None
    items = gen.values(timeout=30) if values else (ray_tpu.get(r, timeout=30) for r in gen)
    for i, item in enumerate(items):
        out.append(item)
        now = time.perf_counter()
        if report and t_prev is not None:
            gen.report_delivered([now - t_prev])
        t_prev = now
        if sleep_every and (i + 1) % sleep_every == 0:
            time.sleep(sleep_s)
    return out


def _tiny_llm() -> dict:
    """What ``LLMDeployment`` / ``build_llm_app`` take for a two-layer GPT-J."""
    from ray_tpu.llm import EngineConfig
    from ray_tpu.models.gptj import GPTJConfig

    return dict(
        model="gptj",
        model_cfg=GPTJConfig(
            vocab_size=128, seq_len=64, d_model=32, n_layers=2, n_heads=2,
            rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
            fused_loss=False,
        ),
        engine_config=EngineConfig(
            max_slots=2, num_blocks=32, block_size=4, max_blocks_per_seq=12,
            prefill_chunk=8,
        ),
    )


@pytest.fixture
def producer():
    ray_tpu.init(num_cpus=4)
    actor = Producer.remote()
    yield actor, ray_tpu.get(actor.totals.remote(), timeout=60)
    ray_tpu.shutdown()


@READS
def test_every_station_counts_each_stream(producer, values):
    """(a) two streams of 30 and 12 items through a local head, the
    consumer reporting: a gap an item from each stream's second on at
    ``sent``, ``acked`` and ``written``; ``head_hold`` one an item."""
    actor, before = producer
    for n in (30, 12):
        gen = actor.items.options(num_returns="streaming").remote(n)
        assert _consume(gen, values=values) == list(range(n))
        gen.close()  # (its last acks leave before its disposal)
    got = _gained(actor, before)
    assert got["sent"] == got["acked"] == got["written"] == 29 + 11, got
    assert got["head_hold"] == 42, got
    assert got["emit"] == got["wake"] == 0  # no engine in this process


@READS
def test_a_slow_consumer_shows_downstream_only(producer, values):
    """(b) a consumer that sleeps 80 ms before every sixth ask (and stays
    inside the window of 16): the acks come late, the producer sends every
    8 ms as before.  Read by reference the items lie in the HEAD meanwhile
    (``head_hold``); read by value they are pushed on arrival and lie in the
    consumer's inbox, so the head holds none for long."""
    actor, before = producer
    gen = actor.items.options(num_returns="streaming").remote(30, 0.008)
    assert len(_consume(gen, sleep_every=6, sleep_s=0.08, values=values)) == 30
    gen.close()
    got = _gained(actor, before)
    assert got["slow"]["acked"] >= 3, got
    assert got["slow"]["head_hold"] == 0 if values else got["slow"]["head_hold"] >= 3, got
    assert got["slow"]["sent"] <= 1 and got["waits"] == 0, got  # 1: a loaded CI host


def test_a_slow_generator_body_shows_at_sent(producer):
    """(b) a body that sleeps inside the handler thread: the gap is there
    from ``sent`` on (an engine's ``emit`` would not show it: the loop
    thread is another; here there is no engine, so ``emit`` stays empty)."""
    actor, before = producer
    gen = actor.items.options(num_returns="streaming").remote(8, 0.05)
    assert len(_consume(gen)) == 8
    got = _gained(actor, before)
    assert got["slow"]["sent"] == 7 and got["slow"]["acked"] >= 6, got
    assert got["slow"]["head_hold"] <= 1 and got["emit"] == 0, got


@READS
def test_a_plain_consumer_leaves_written_empty(producer, values):
    """(f) a consumer that reports nothing streams as before."""
    actor, before = producer
    gen = actor.items.options(num_returns="streaming").remote(25)
    assert _consume(gen, report=False, values=values) == list(range(25))
    gen.close()
    got = _gained(actor, before)
    assert got["written"] == 0 and got["sent"] == got["acked"] == 24, got


@READS
def test_backpressure_waits_are_counted(monkeypatch, values):
    """(c) a window of 2 and a consumer slower than the producer: the
    producer waits for acks, and says how often and how long (read by value
    too: an item pushed is not an item taken)."""
    # workers read the flag from their environment
    monkeypatch.setenv("RAY_TPU_STREAMING_BACKPRESSURE_ITEMS", "2")
    # (``init`` reads it into this process's config too: put that back after)
    monkeypatch.setattr(GLOBAL_CONFIG, "streaming_backpressure_items", int("2"))
    ray_tpu.init(num_cpus=4)
    try:
        actor = Producer.remote()
        before = ray_tpu.get(actor.totals.remote(), timeout=60)
        gen = actor.items.options(num_returns="streaming").remote(12)
        assert len(_consume(gen, sleep_every=1, sleep_s=0.02, values=values)) == 12
        got = _gained(actor, before)
        assert got["waits"] > 0 and got["wait_s"] > 0.02, got
    finally:
        ray_tpu.shutdown()


def test_percentile_from_the_buckets_is_within_a_quarter_ms():
    """(e) the 95th percentile read from the fine boundaries against the
    sorted sample's, for gaps as the serving cells have them."""
    rng = random.Random(38)
    for median_ms, sigma in ((16.0, 0.35), (25.0, 0.12), (38.0, 0.2)):
        h = Histogram(f"t_fine_{int(median_ms)}", "", boundaries=FINE_LATENCY_BOUNDS_S)
        series = h.bind()
        xs = sorted(
            min(rng.lognormvariate(0.0, sigma) * median_ms, 63.0) / 1e3
            for _ in range(20000)
        )
        for x in xs:
            series.observe(x)
        for q in (0.5, 0.95, 0.99):
            exact = xs[int(q * len(xs)) - 1]
            est = percentiles_from_buckets(FINE_LATENCY_BOUNDS_S, series.buckets(), q)
            assert abs(est - exact) < 0.25e-3, (median_ms, q, est, exact)
    assert len(FINE_LATENCY_BOUNDS_S) == 140
    assert FINE_LATENCY_BOUNDS_S[127] == pytest.approx(0.064)
    assert FINE_LATENCY_BOUNDS_S[-1] == pytest.approx(4.096)


def test_observe_takes_the_first_bound_not_below():
    """``observe`` moved from a linear scan to ``bisect``: a value ON a
    boundary still lands in that boundary's bucket, one past the last in
    the overflow."""
    h = Histogram("t_fine_edges", "", boundaries=(0.001, 0.002, 0.004))
    for v in (0.0005, 0.001, 0.0011, 0.002, 0.004, 0.0041, 9.0):
        h.observe(v)
    assert h.buckets() == [2, 2, 1, 2]


def test_llm_deployment_stats_carry_the_stations():
    """(d) a tiny ``LLMDeployment`` behind the HTTP proxy: every key of
    ``stats()["stream"]`` on one ``bounds_s``, the proxy's write gaps in
    ``written``, and the section readable while the engine's lock is held."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    ray_tpu.init(num_cpus=8, num_tpus=0)
    try:
        app = build_llm_app(**_tiny_llm())
        handle = serve.run(app, name="llm", http=True, http_port=0)
        controller = ray_tpu.get_actor("SERVE_CONTROLLER")
        port = ray_tpu.get(controller.get_proxy_port.remote(), timeout=30)
        n_new = 40
        before = handle.stats.remote().result(timeout=60)["stream"]  # warm-up's tokens
        for seed in (1, 2):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/llm",
                data=json.dumps({"prompt": [3, 4, 5, 6 + seed], "max_tokens": n_new}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert len(resp.read().split()) == n_new
        deadline = time.time() + 10
        per_stream = n_new - 1
        while True:
            stream = handle.stats.remote().result(timeout=60)["stream"]
            gained = {k: sum(stream[k]) - sum(before[k]) for k in STATION_KEYS}
            if gained["acked"] >= 2 * per_stream or time.time() > deadline:
                break
            time.sleep(0.05)
        assert set(stream) == set(STATION_KEYS) | {"bounds_s", "backpressure", "batch", "ack"}
        assert stream["bounds_s"] == list(FINE_LATENCY_BOUNDS_S)
        for k in STATION_KEYS:
            assert len(stream[k]) == len(stream["bounds_s"]) + 1, k
        assert gained["emit"] == gained["sent"] == gained["acked"] == 2 * per_stream, gained
        assert gained["wake"] == gained["head_hold"] == 2 * n_new, gained
        # the stream thread hands over what is written when it asks for the
        # next item: a stream's last gaps can miss its last ask
        assert 2 * per_stream - 6 <= gained["written"] <= 2 * per_stream, gained
        assert set(stream["backpressure"]) == {"waits", "wait_s"}
        # the replica's streams take the batched path: every token left in
        # a ``stream_items`` message (a consumer late to ask has several of
        # its tokens ride one message, once its window opens)
        batch = {k: stream["batch"][k] - before["batch"][k] for k in stream["batch"]}
        assert batch["items"] == 2 * n_new, batch
        assert 0 < batch["sends"] <= batch["streams"] <= batch["items"], batch
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_a_steps_rows_leave_the_replica_together():
    """``sent`` / ``wake`` / ``batch`` on the batched path: two streams on a
    two-slot engine decode side by side, so a message carries a token of
    each while both run; ``wake`` counts every token once, ``sent`` a gap a token from each
    stream's second on, and the tokens are the blocking call's."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    ray_tpu.init(num_cpus=8, num_tpus=0)
    try:
        handle = serve.run(build_llm_app(**_tiny_llm()), name="llm2")
        n_new, prompts = 40, ([3, 4, 5, 7], [9, 8, 7, 6])
        want = [handle.generate.remote(p, max_tokens=n_new).result(timeout=120) for p in prompts]
        before = handle.stats.remote().result(timeout=60)["stream"]
        got = [None, None]

        def one(i):
            got[i] = list(handle.options(stream=True).remote(prompts[i], max_tokens=n_new))

        threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert got == want
        deadline = time.time() + 10
        while True:
            stream = handle.stats.remote().result(timeout=60)["stream"]
            gained = {k: sum(stream[k]) - sum(before[k]) for k in STATION_KEYS}
            if gained["acked"] >= 2 * (n_new - 1) or time.time() > deadline:
                break
            time.sleep(0.05)
        batch = {k: stream["batch"][k] - before["batch"][k] for k in stream["batch"]}
        assert batch["items"] == gained["wake"] == 2 * n_new, (batch, gained)
        assert gained["sent"] == gained["emit"] == 2 * (n_new - 1), gained
        # side by side for part of their 40 steps: fewer messages than tokens
        assert 0 < batch["sends"] < batch["items"] and batch["streams"] <= batch["items"], batch
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_the_engine_hands_a_steps_tokens_over_once():
    """A request with a ``sink``: its tokens go there and not through
    ``req.stream``, a step that emitted asks for ONE flush once every row's
    token is pushed, and ``stream_tokens`` sees the end marker alone."""
    from ray_tpu.llm.scheduler import SamplingParams
    from ray_tpu.serve.llm import LLMDeployment

    dep = LLMDeployment(warmup=False, **_tiny_llm())
    dep._stop.set()  # drive the engine by hand
    dep._engine.start_watchdog().stop()
    dep._loop.join(timeout=10)
    eng = dep._engine
    sp = SamplingParams(max_tokens=12)
    want = eng.generate([3, 4, 5, 6], sp)
    flushes, unflushed, toks = [], [0, 0], ([], [])

    class Sink:  # what the engine asks of ``stream_sink.Sink``
        def __init__(self, i):
            self.i = i

        def push(self, tok, t_emit):
            toks[self.i].append(tok)
            unflushed[self.i] += 1

        def flush_soon(self):
            flushes.append(tuple(unflushed))
            unflushed[:] = [0, 0]

    reqs = [eng.submit([3, 4, 5, 6 + i], sp, sink=Sink(i)) for i in (0, 1)]
    while not all(r.finished for r in reqs):
        eng.step()
    assert toks[0] == want and len(toks[1]) == len(want)
    assert unflushed == [0, 0], "a token pushed and no flush asked for"
    # one ask a step that emitted, both rows' tokens behind it: never an
    # ask a row (each first token comes out of turn, in an ask of its own)
    assert len(flushes) <= len(want) + 4 and (1, 1) in flushes, flushes
    for r in reqs:
        assert list(eng.stream_tokens(r, timeout=5)) == []  # the end marker alone


def test_the_stream_section_needs_no_engine_lock():
    """(d) ``stats()["stream"]`` is the metric registry's, not the
    engine's: while a step holds ``LLMEngine._lock`` the engine's own
    counters wait for it, the stream section does not."""
    from ray_tpu.llm import engine as eng
    from ray_tpu.serve.llm import LLMDeployment

    dep = LLMDeployment(warmup=False, **_tiny_llm())
    whole = []
    try:
        with dep._engine._lock:  # what a step holds
            t = threading.Thread(target=lambda: whole.append(dep.stats()))
            t.start()
            t0 = time.perf_counter()
            section = eng.stream_stats()
            assert time.perf_counter() - t0 < 1.0
            t.join(timeout=0.3)
            assert t.is_alive() and not whole  # the engine's part waits
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        dep._stop.set()
        dep._engine.start_watchdog().stop()  # the one the deployment started
        dep._loop.join(timeout=10)
    assert not dep._loop.is_alive()
    assert set(section) == set(STATION_KEYS) | {"bounds_s", "backpressure", "batch", "ack"}
    assert set(whole[0]["stream"]) == set(section) and "steps" in whole[0]


def test_obs_top_shows_emit_and_written_side_by_side():
    """``obs top``'s ITL line: what the engine made beside what a client
    got; ``—`` where no consumer has reported."""
    from ray_tpu.obs import itl_top_row

    emit = {"p50": 0.0247, "p95": 0.0252, "p99": 0.046, "count": 900, "sum": 22.0}
    written = dict(emit, p95=0.0301)
    row = itl_top_row(emit, {'{"station":"sent"}': emit, '{"station":"written"}': written})
    assert row.startswith("ITL:") and "written:" in row
    assert "25.2ms" in row.split("written:")[0] and "30.1ms" in row.split("written:")[1]
    assert itl_top_row(emit, {}).endswith("written: —")
