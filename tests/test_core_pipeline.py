"""Pipelined submission & batched reply plane (ISSUE 14).

* **FIFO matrix**: execution order equals submission order across every
  batching seam — driver dispatch coalescing (``run_task_batch``),
  worker-side submit windows (``submit_batch``), reply coalescing
  (``tasks_done_batch``), and interleaved actor+task bursts (each
  stream's own FIFO holds; no contract spans streams).
* **Async error surfacing**: submission is fire-and-forget, so
  submit-time failures (dead actor, oversized inline spec) resolve on
  the RETURN refs — the ``.remote()`` call site never raises.
* **Waterfall integrity**: sampled tasks that rode batched legs still
  fold all 7 legs (8 stamps) with monotonic timestamps — batching moves
  WHERE a stamp is taken, never whether.
* **Batch telemetry**: ``core_submit_batch_size`` sees real windows and
  the ``obs top`` row honors the below-2-samples ``—`` contract.
* **Chaos**: the head socket dying mid-burst resolves EVERY in-flight
  ref to a result or a retriable error — never a hang (fail-not-replay
  is the pinned semantic for un-acked submit windows: a blind replay of
  a window the head DID process would double-submit its tasks).
"""

import threading
import time

import pytest

import ray_tpu
from conftest import tcp_head_child
from ray_tpu import exceptions as rex
from ray_tpu.util import metrics as um
from ray_tpu.util import tracing
from ray_tpu.util import waterfall as wfl


def _hist(name: str) -> dict:
    """First (sole) series of a histogram's percentile snapshot."""
    for v in um.histogram_percentiles(name).get(name, {}).values():
        return v
    return {"count": 0, "sum": 0.0}


@ray_tpu.remote
class Recorder:
    def __init__(self):
        self.order = []

    def add(self, i):
        self.order.append(i)

    def snapshot(self):
        return list(self.order)


# ---------------------------------------------------------------------------
# FIFO ordering across batch seams
# ---------------------------------------------------------------------------


class TestFifoUnderBatching:
    def test_actor_burst_preserves_submission_order(self, ray_start_regular):
        """A driver-side burst of actor calls (no gets in between) rides
        coalesced run_task_batch dispatches; per-actor FIFO must hold."""
        r = Recorder.remote()
        refs = [r.add.remote(i) for i in range(200)]
        ray_tpu.get(refs, timeout=120)
        assert ray_tpu.get(r.snapshot.remote(), timeout=60) == list(range(200))

    def test_worker_submit_window_preserves_actor_fifo(self, ray_start_regular):
        """A WORKER fan-out rides the pipelined submit_batch path (window
        flow control + header split); the head processes each window in
        submission order, so per-actor FIFO survives the batching."""
        r = Recorder.remote()

        @ray_tpu.remote
        def fan(rec, n):
            got = [rec.add.remote(i) for i in range(n)]
            ray_tpu.get(got)
            return n

        base = _hist("core_submit_batch_size")
        assert ray_tpu.get(fan.remote(r, 128), timeout=120) == 128
        assert ray_tpu.get(r.snapshot.remote(), timeout=60) == list(range(128))
        # the burst really rode submit windows: the head observed them
        after = _hist("core_submit_batch_size")
        assert after["count"] > base["count"]
        # and the window sizes sum to (at least) the burst's tasks
        assert after["sum"] - base["sum"] >= 128

    def test_single_worker_lease_chain_fifo(self, tmp_path):
        """One CPU slot = one worker: a task burst drains through lease
        chains and coalesced dispatch batches in strict submission
        order (append-only file records execution order)."""
        ray_tpu.init(num_cpus=1, num_tpus=0)
        try:
            path = str(tmp_path / "order.txt")

            @ray_tpu.remote
            def mark(p, i):
                with open(p, "a") as f:
                    f.write(f"{i}\n")
                return i

            refs = [mark.remote(path, i) for i in range(100)]
            assert ray_tpu.get(refs, timeout=120) == list(range(100))
            with open(path) as f:
                seen = [int(line) for line in f]
            assert seen == list(range(100))
        finally:
            ray_tpu.shutdown()

    def test_interleaved_actor_and_task_bursts(self, tmp_path):
        """Interleaved actor calls and plain tasks: each stream keeps its
        OWN FIFO (per-actor, per-worker) across shared batch messages."""
        ray_tpu.init(num_cpus=1, num_tpus=0)
        try:
            path = str(tmp_path / "order.txt")
            r = Recorder.remote()

            @ray_tpu.remote
            def mark(p, i):
                with open(p, "a") as f:
                    f.write(f"{i}\n")

            refs = []
            for i in range(60):
                refs.append(r.add.remote(i))
                refs.append(mark.remote(path, i))
            ray_tpu.get(refs, timeout=120)
            assert ray_tpu.get(r.snapshot.remote(), timeout=60) == list(range(60))
            with open(path) as f:
                assert [int(line) for line in f] == list(range(60))
        finally:
            ray_tpu.shutdown()


class TestHeaderSplit:
    def test_streaming_actor_method_mints_header(self, ray_start_regular):
        """num_returns='streaming' actor calls ride the header-split path
        too — the content-derived id must accept the STRING (a %d format
        crashed exactly here once) and the stream must work end to end."""

        @ray_tpu.remote
        class Gen:
            @ray_tpu.method(num_returns="streaming")
            def count(self, n):
                for i in range(n):
                    yield i

        g = Gen.remote()
        got = [ray_tpu.get(r) for r in g.count.remote(4)]
        assert got == [0, 1, 2, 3]
        # twice: the second call rides the cached header reference
        got = [ray_tpu.get(r) for r in g.count.remote(3)]
        assert got == [0, 1, 2]

    def test_header_ids_stable_across_handle_copies(self, ray_start_regular):
        """Deserialized handle copies must mint the SAME header id for the
        same method (content-derived, not per-instance random) — receiver
        caches dedupe instead of growing one entry per copy."""
        r = Recorder.remote()
        ray_tpu.get(r.add.remote(0), timeout=60)
        hid1 = r._hdr_cache[("add", 1)][0]
        import pickle as _pickle

        r2 = _pickle.loads(_pickle.dumps(r))
        ray_tpu.get(r2.add.remote(1), timeout=60)
        assert r2._hdr_cache[("add", 1)][0] == hid1


# ---------------------------------------------------------------------------
# async submit-error surfacing on refs
# ---------------------------------------------------------------------------


class TestAsyncSubmitErrors:
    def test_dead_actor_surfaces_on_ref(self, ray_start_regular):
        """Calling a dead actor must not raise at the .remote() call site
        (submission is fire-and-forget); the error resolves on the ref."""
        r = Recorder.remote()
        ray_tpu.get(r.add.remote(0), timeout=60)
        ray_tpu.kill(r)
        ref = r.add.remote(1)  # call site must NOT raise
        with pytest.raises(rex.RayActorError):
            ray_tpu.get(ref, timeout=60)

    def test_dead_actor_surfaces_on_ref_from_worker(self, ray_start_regular):
        """Same contract through the socket submit_batch path: a worker's
        window item for a dead actor fails that ITEM's refs — the window
        itself completes and is acked (credits can never wedge)."""
        r = Recorder.remote()
        ray_tpu.get(r.add.remote(0), timeout=60)
        ray_tpu.kill(r)

        @ray_tpu.remote
        def poke(rec):
            ref = rec.add.remote(1)  # must not raise here either
            try:
                ray_tpu.get(ref, timeout=30)
                return "no-error"
            except rex.RayActorError:
                return "actor-error"

        assert ray_tpu.get(poke.remote(r), timeout=120) == "actor-error"

    def test_oversized_inline_spec_fails_on_ref(self, ray_start_regular, monkeypatch):
        """A window item whose inline (by-value) argument bytes exceed
        core_max_spec_inline_bytes resolves its refs to a ValueError that
        says to put() the argument — asynchronously, without poisoning
        the rest of the window."""
        from ray_tpu._private.config import GLOBAL_CONFIG

        monkeypatch.setattr(GLOBAL_CONFIG, "core_max_spec_inline_bytes", 4096)

        @ray_tpu.remote
        def fan_big():
            @ray_tpu.remote
            def eat(b):
                return len(b)

            # 32KB stays under the auto-put threshold, so it ships inline
            # in the submit window and trips the head-side cap
            big = eat.remote(b"x" * 32768)
            ok = eat.remote(b"y" * 16)  # same window, small: must succeed
            assert ray_tpu.get(ok, timeout=30) == 16
            try:
                ray_tpu.get(big, timeout=30)
                return "no-error"
            except Exception as e:  # noqa: BLE001 - asserting the message
                return f"error:{e}"

        out = ray_tpu.get(fan_big.remote(), timeout=120)
        assert out.startswith("error:") and "put()" in out


# ---------------------------------------------------------------------------
# waterfall integrity under batching
# ---------------------------------------------------------------------------


class TestWaterfallUnderBatching:
    def test_batched_tasks_fold_all_phases_monotonic(self, ray_start_regular):
        """Sampled tasks that rode submit windows, coalesced dispatches,
        and reply batches still fold ALL 7 legs with monotonic stamps —
        no phase is silently dropped by batching."""
        wfl.clear()
        from ray_tpu._private.runtime import get_ctx

        @ray_tpu.remote
        def leaf(i):
            return i

        @ray_tpu.remote
        def fan(n):
            return sum(ray_tpu.get([leaf.remote(i) for i in range(n)]))

        before = get_ctx().call("waterfall")["folded"]
        with tracing.trace_context() as rid:
            assert ray_tpu.get(fan.remote(32), timeout=120) == sum(range(32))
        s = get_ctx().call("waterfall", recent=64)
        assert s["folded"] - before == 33  # 32 batched leaves + the parent
        assert s["incomplete"] == 0
        ours = [rec for rec in s["recent"] if rec.get("request_id") == rid]
        assert len(ours) >= 33
        for rec in ours:
            stamps = rec["stamps"]
            assert len(stamps) == len(wfl.PHASES)
            assert stamps == sorted(stamps), (
                f"non-monotone stamps for {rec.get('name')}: {stamps}"
            )
            assert all(v >= 0 for v in rec["legs"].values())


# ---------------------------------------------------------------------------
# batch telemetry
# ---------------------------------------------------------------------------


class TestBatchTelemetry:
    def test_reply_batches_observed(self, ray_start_regular):
        """A burst of short actor calls coalesces completions into
        tasks_done_batch messages; the head's size histogram sees them.
        Coalescing is load-dependent (the off-path flusher drains
        whatever accumulated), so drive bursts until one lands."""
        base = _hist("core_reply_batch_size")
        r = Recorder.remote()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            ray_tpu.get([r.add.remote(i) for i in range(256)], timeout=120)
            if _hist("core_reply_batch_size")["count"] > base["count"]:
                return
        pytest.fail("no coalesced reply batch observed after repeated bursts")

    def test_core_batch_top_row_contract(self):
        """obs top's core-batch row: absent without the metrics, and a
        histogram below 2 samples renders the `—` placeholder."""
        from ray_tpu.obs import core_batch_top_row

        assert core_batch_top_row({}, {}) is None
        metrics = {
            "core_submit_batch_size": {"": 1.0},
            "core_submit_credits": {"": 4096.0},
        }
        pcts = {"core_submit_batch_size": {"": {"count": 1, "p50": 1.0, "p99": 1.0}}}
        row = core_batch_top_row(metrics, pcts)
        assert row is not None
        assert "submit=—" in row and "reply=—" in row
        assert "credits=4096" in row
        pcts = {
            "core_submit_batch_size": {"": {"count": 9, "p50": 8.0, "p99": 32.0}},
            "core_reply_batch_size": {"": {"count": 4, "p50": 2.0, "p99": 4.0}},
        }
        row = core_batch_top_row(metrics, pcts)
        assert "submit=8/32" in row and "reply=2/4" in row


# ---------------------------------------------------------------------------
# chaos: head socket death mid-burst
# ---------------------------------------------------------------------------


@pytest.fixture
def tcp_head():
    with tcp_head_child(reconnect_grace_s=5) as addr:
        yield addr


class TestChaosMidBurst:
    def test_socket_death_mid_burst_resolves_every_ref(self, tcp_head):
        """Kill the driver↔head socket while a submit burst is in flight:
        every ref must resolve — a result (the head processed its window
        before the cut, or after the token redial) or a retriable error
        (un-acked window / unsent buffer, failed not replayed) — and
        NEVER hang. The plane must keep working after the redial."""
        ray_tpu.init(address=f"ray://{tcp_head}")
        try:
            from ray_tpu._private.node_agent import shutdown_conn
            from ray_tpu._private.runtime import get_ctx

            @ray_tpu.remote
            def f(i):
                return i

            ctx = get_ctx()
            refs = []

            def burst():
                for i in range(400):
                    refs.append(f.remote(i))

            t = threading.Thread(target=burst)
            t.start()
            while len(refs) < 50:  # let real windows get in flight first
                time.sleep(0.001)
            shutdown_conn(ctx.conn)  # violent drop, no goodbye
            t.join(timeout=120)
            assert not t.is_alive(), "submitter wedged after socket death"
            assert len(refs) == 400

            deadline = time.monotonic() + 90
            ok = failed = 0
            for i, ref in enumerate(refs):
                while True:
                    try:
                        assert ray_tpu.get(ref, timeout=60) == i
                        ok += 1
                        break
                    except rex.GetTimeoutError:
                        pytest.fail(f"ref {i} hung after mid-burst socket death")
                    except rex.RayError as e:
                        if "while sending" in str(e) and time.monotonic() < deadline:
                            # transient send-into-dying-socket error during
                            # the redial window — the pinned contract says
                            # retry, so the test does
                            time.sleep(0.2)
                            continue
                        failed += 1
                        break
            assert ok + failed == 400
            # a poisoned (failed-submit) ref counts READY for wait():
            # waiters drain instead of spinning on ids the head never saw
            while True:
                try:
                    _ready, not_ready = ray_tpu.wait(
                        refs, num_returns=len(refs), timeout=30
                    )
                    break
                except rex.RayError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.3)
            assert not not_ready

            # the plane recovered: fresh tasks run on the same session
            while True:
                try:
                    assert ray_tpu.get(f.remote(12345), timeout=60) == 12345
                    break
                except rex.GetTimeoutError:
                    pytest.fail("post-recovery task hung")
                except rex.RayError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.3)
        finally:
            ray_tpu.shutdown()

    def test_socket_death_mid_put_burst_replays_every_put(self, tcp_head):
        """Puts are the at-most-once EXCEPTION: a put id is minted exactly
        once per op, so un-acked/unsent puts at a socket drop are REPLAYED
        on the fresh conn (head dedupes replay-flagged redelivery) instead
        of poisoned like tasks. Every ref must resolve to its VALUE — not
        a retriable error — once the redial lands."""
        ray_tpu.init(address=f"ray://{tcp_head}")
        try:
            from ray_tpu._private.node_agent import shutdown_conn
            from ray_tpu._private.runtime import get_ctx

            ctx = get_ctx()
            refs = []

            def burst():
                for i in range(200):
                    refs.append(ray_tpu.put({"i": i}))

            t = threading.Thread(target=burst)
            t.start()
            while len(refs) < 25:  # let real windows get in flight first
                time.sleep(0.001)
            shutdown_conn(ctx.conn)  # violent drop, no goodbye
            t.join(timeout=120)
            assert not t.is_alive(), "putter wedged after socket death"
            assert len(refs) == 200

            deadline = time.monotonic() + 90
            for i, ref in enumerate(refs):
                while True:
                    try:
                        assert ray_tpu.get(ref, timeout=60) == {"i": i}
                        break
                    except rex.GetTimeoutError:
                        pytest.fail(f"put {i} hung after mid-burst socket death")
                    except rex.RayError as e:
                        # transient send-into-dying-socket errors during the
                        # redial window retry; a POISONED put would repeat
                        # forever and trip the deadline — that's the failure
                        # this test exists to catch
                        if time.monotonic() > deadline:
                            pytest.fail(f"put {i} never resolved to its value: {e}")
                        time.sleep(0.2)
        finally:
            ray_tpu.shutdown()
