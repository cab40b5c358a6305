"""Engine-level robustness: token-exact resume, watchdog, shedding.

The serve-plane chaos suite (tests/test_serve_chaos.py) proves these
survive real SIGKILLs through the full serve stack; this file pins the
underlying engine primitives (RESILIENCE.md):

* ``submit(resume_tokens=...)`` continues a partial generation
  TOKEN-IDENTICALLY — greedy and seeded sampling, at every cut point —
  because per-token PRNG keys derive from (seed, absolute output index),
  never from where a window or a failover boundary fell;
* the watchdog reaps cancelled/deadline-blown requests with the engine
  lock when it can, and unblocks their stream consumers WITHOUT it when
  the step loop is wedged holding it;
* the KV-pool ledger audit catches leaked, duplicated, and orphaned
  blocks;
* deadline-aware admission sheds doomed work with ``OverloadedError``
  (+ retry_after_s) instead of queueing it;
* ``stream_tokens`` timeouts carry the stall diagnosis
  (``EngineStalledError``).
"""

import queue
import threading
import time

import numpy as np
import pytest

import jax

from ray_tpu.exceptions import OverloadedError
from ray_tpu.llm import (
    EngineConfig,
    EngineStalledError,
    EngineWatchdog,
    LLMEngine,
    SamplingParams,
)
from ray_tpu.models.gptj import GPTJConfig, gptj_init

TINY = GPTJConfig(
    vocab_size=128, seq_len=64, d_model=32, n_layers=2, n_heads=2,
    rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
    fused_loss=False,
)


@pytest.fixture(scope="module")
def tiny_params():
    return gptj_init(jax.random.PRNGKey(0), TINY)


def _engine(params, **kw):
    defaults = dict(
        max_slots=3, num_blocks=32, block_size=4, max_blocks_per_seq=12,
        prefill_chunk=8,
    )
    defaults.update(kw)
    return LLMEngine(TINY, params, EngineConfig(**defaults))


@pytest.fixture(scope="module")
def shared_engine(tiny_params):
    """One engine for the resume-identity tests (fresh engines re-jit;
    resume correctness is host-side bookkeeping, so sharing is safe as
    long as each test leaves it drained)."""
    return _engine(tiny_params)


def _drain(eng, req):
    """Step the engine until ``req`` finishes; returns the streamed tokens
    (only what was produced AFTER submission — a resumed prefix is not
    re-streamed)."""
    got = []
    deadline = time.time() + 60
    while not req.finished:
        eng.step()
        assert time.time() < deadline, "engine made no progress"
    while True:
        try:
            kind, val = req.stream.get_nowait()[:2]  # a token carries its emit stamp third
        except queue.Empty:
            break
        if kind == "token":
            got.append(val)
        else:
            break
    return got


PROMPT = [5, 6, 7, 5, 6, 7, 5, 6, 7]

GREEDY = SamplingParams(max_tokens=20)
SAMPLED = SamplingParams(max_tokens=20, temperature=0.8, top_k=5, top_p=0.9,
                         seed=1234)


class TestResumeTokens:
    @pytest.mark.parametrize("params", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    def test_resume_is_token_identical_at_every_cut(self, shared_engine, params):
        """The failover invariant: resuming from ANY delivered prefix
        reproduces the unkilled run exactly — greedy and seeded sampling."""
        eng = shared_engine
        full = eng.generate(PROMPT, params)
        assert len(full) == params.max_tokens
        for cut in (0, 1, 7, params.max_tokens - 1, params.max_tokens):
            req = eng.submit(PROMPT, params, resume_tokens=full[:cut])
            got = _drain(eng, req)
            assert full[:cut] + got == full, f"cut={cut}"
            # the resumed prefix is never re-streamed
            assert len(got) == params.max_tokens - cut

    def test_resume_after_final_token_finishes_immediately(self, shared_engine):
        """Replica died between the last token and the done sentinel: the
        resume must finish without touching the scheduler."""
        eng = shared_engine
        full = eng.generate(PROMPT, GREEDY)
        before = eng.scheduler.finish_count
        req = eng.submit(PROMPT, GREEDY, resume_tokens=full)
        assert req.finished and req.finish_reason == "length"
        assert req.stream.get_nowait() == ("done", "length")
        assert eng.scheduler.finish_count == before  # never entered

    def test_resume_on_delivered_stop_token(self, shared_engine):
        eng = shared_engine
        # the stop token is one this model does emit for this prompt, taken
        # from an unconstrained run (no literal of a seeded toy model's)
        free = eng.generate(PROMPT, SamplingParams(max_tokens=20))
        stop = free[5]
        sp = SamplingParams(max_tokens=20, stop_token_ids=(stop,))
        full = eng.generate(PROMPT, sp)
        assert full == free[: free.index(stop) + 1] and full[-1] == stop
        req = eng.submit(PROMPT, sp, resume_tokens=full)
        assert req.finished and req.finish_reason == "stop"

    def test_resume_survives_preemption(self, tiny_params):
        """A resumed request that then gets PREEMPTED re-prefills
        prompt + resumed + new tokens and still matches the reference —
        the two recovery mechanisms compose."""
        eng = _engine(tiny_params, max_slots=2, num_blocks=14,
                      max_blocks_per_seq=10)
        full = eng.generate(PROMPT, GREEDY)
        # resume, then saturate the pool so the resumed request gets evicted
        req = eng.submit(PROMPT, GREEDY, resume_tokens=full[:6])
        rival = eng.submit(_rand_prompt(8), SamplingParams(max_tokens=20))
        got = _drain(eng, req)
        _drain(eng, rival)
        assert full[:6] + got == full
        assert eng.pool.audit()["ok"]

    def test_resume_validation(self, shared_engine):
        with pytest.raises(ValueError, match="resume_tokens"):
            shared_engine.submit(
                PROMPT, SamplingParams(max_tokens=4), resume_tokens=[1] * 5
            )


def _rand_prompt(n, seed=3):
    return list(np.random.RandomState(seed).randint(0, TINY.vocab_size, n))


class TestWatchdog:
    def test_reaps_deadline_and_cancel_with_lock(self, tiny_params):
        """Nobody driving step(): the watchdog alone frees slots/blocks of
        doomed requests through the scheduler."""
        eng = _engine(tiny_params)
        wd = EngineWatchdog(eng, stall_deadline_s=30.0)
        r1 = eng.submit(PROMPT, SamplingParams(max_tokens=4), deadline_s=0.0)
        r2 = eng.submit(PROMPT, SamplingParams(max_tokens=4))
        eng.cancel(r2.id)
        info = wd.check_once()
        assert info["reaped"] == 2 and info["unblocked"] == 0
        assert r1.finished and r1.finish_reason == "deadline"
        assert r2.finished and r2.finish_reason == "cancelled"
        assert info["audit"]["ok"]
        assert eng.pool.num_used_blocks == 0  # blocks came back

    def test_wedged_step_unblocks_consumers(self, tiny_params):
        """The step loop is stuck holding the engine lock: the watchdog
        cannot touch scheduler state, but stream consumers of
        deadline-blown requests still get their done sentinel."""
        eng = _engine(tiny_params)
        wd = EngineWatchdog(eng, stall_deadline_s=0.05, lock_timeout_s=0.01)
        req = eng.submit(PROMPT, SamplingParams(max_tokens=4), deadline_s=0.01)
        time.sleep(0.08)
        eng._lock.acquire()  # the wedge
        try:
            info = wd.check_once()
        finally:
            eng._lock.release()
        assert info["stalled"] and info["unblocked"] == 1
        assert req.stream.get_nowait() == ("done", "deadline")
        # a second tick must not double-unblock the same request
        eng._lock.acquire()
        try:
            assert wd.check_once()["unblocked"] == 0
        finally:
            eng._lock.release()

    def test_stall_detection_one_event_per_episode(self, tiny_params):
        eng = _engine(tiny_params)
        wd = EngineWatchdog(eng, stall_deadline_s=0.05)
        eng.submit(PROMPT, SamplingParams(max_tokens=4))
        eng._beat = (time.monotonic() - 1.0, 1)  # fake a wedged step
        assert wd.check_once()["stalled"]
        assert wd.check_once()["stalled"]
        assert wd.stall_count == 1  # episode counted once
        # progress clears the episode; a NEW wedge counts again
        eng.step()
        assert not wd.check_once()["stalled"]
        eng._beat = (time.monotonic() - 1.0, 1)
        wd.check_once()
        assert wd.stall_count == 2

    def test_idle_engine_never_stalls(self, tiny_params):
        eng = _engine(tiny_params)
        wd = EngineWatchdog(eng, stall_deadline_s=0.0)
        info = wd.check_once()
        assert not info["stalled"] and info["pending"] == 0

    def test_leak_audit_detects_orphans_and_duplicates(self, tiny_params):
        eng = _engine(tiny_params)
        wd = EngineWatchdog(eng)
        assert wd.check_once()["audit"]["ok"]
        # an owner with no live request = leaked blocks
        eng.pool.allocate("ghost", 8)
        audit = wd.check_once()["audit"]
        assert not audit["ok"] and audit["orphans"] == ["ghost"]
        assert wd.leak_count == 1
        eng.pool.free("ghost")
        assert wd.check_once()["audit"]["ok"]
        # ledger corruption: the same block on the free list twice
        eng.pool._free.append(eng.pool._free[-1])
        audit = eng.pool.audit()
        assert audit["duplicates"] and audit["missing"] < 0 and not audit["ok"]

    def test_watchdog_thread_lifecycle(self, tiny_params):
        eng = _engine(tiny_params)
        wd = eng.start_watchdog()
        assert wd.is_alive()
        assert eng.start_watchdog() is wd  # idempotent
        wd.stop()
        assert not wd.is_alive()


class TestShedding:
    def test_doomed_deadline_is_shed_with_retry_after(self, tiny_params):
        eng = _engine(tiny_params)
        eng._rate = 50.0  # measured service rate: 50 tokens/s
        for _ in range(3):
            eng.submit(PROMPT, SamplingParams(max_tokens=20))
        # backlog is 60 promised tokens ≈ 1.2s; a 0.1s deadline is doomed
        with pytest.raises(OverloadedError) as ei:
            eng.submit(PROMPT, SamplingParams(max_tokens=20), deadline_s=0.1)
        assert ei.value.retry_after_s > 0
        # ...but a generous deadline is admitted
        req = eng.submit(PROMPT, SamplingParams(max_tokens=20), deadline_s=60.0)
        assert req.state == "waiting"

    def test_no_rate_evidence_never_sheds(self, tiny_params):
        eng = _engine(tiny_params)
        assert eng._rate == 0.0
        req = eng.submit(PROMPT, SamplingParams(max_tokens=20), deadline_s=0.001)
        assert req in list(eng.scheduler.waiting)

    def test_no_deadline_never_sheds(self, tiny_params):
        eng = _engine(tiny_params)
        eng._rate = 1.0
        for _ in range(4):
            eng.submit(PROMPT, SamplingParams(max_tokens=20))
        assert eng.scheduler.num_waiting == 4

    def test_shed_disabled_by_config(self, tiny_params):
        eng = _engine(tiny_params, shed=False)
        eng._rate = 50.0
        for _ in range(3):
            eng.submit(PROMPT, SamplingParams(max_tokens=20))
        req = eng.submit(PROMPT, SamplingParams(max_tokens=20), deadline_s=0.01)
        assert not req.finished

    def test_service_rate_tracks_generation_and_resets_idle(self, tiny_params):
        eng = _engine(tiny_params)
        # sustained generation (> the 0.5s sampling window) measures a rate
        deadline = time.time() + 30
        while eng.stats()["service_rate_tokens_per_s"] <= 0:
            eng.generate(PROMPT, SamplingParams(max_tokens=20))
            assert time.time() < deadline, "rate never measured"
        # going idle RESETS it (no evidence ≠ slow): the next burst's first
        # request must not be shed on a stale decayed rate. Two idle
        # sampling windows: the first still counts the burst's tail tokens,
        # the second sees zero generation with no work and zeroes the rate.
        for _ in range(2):
            time.sleep(0.6)
            eng.step()
        assert eng.stats()["service_rate_tokens_per_s"] == 0.0
        req = eng.submit(PROMPT, SamplingParams(max_tokens=4), deadline_s=0.5)
        assert not req.finished  # admitted, not shed

    def test_empty_engine_never_sheds_despite_stale_rate(self, tiny_params):
        eng = _engine(tiny_params)
        eng._rate = 0.001  # pathologically stale-low rate, zero backlog
        req = eng.submit(PROMPT, SamplingParams(max_tokens=20), deadline_s=0.5)
        assert not req.finished  # no backlog -> no shedding evidence


class TestEngineStalledError:
    def test_timeout_carries_diagnosis(self, tiny_params):
        eng = _engine(tiny_params)
        req = eng.submit(PROMPT, SamplingParams(max_tokens=4))
        with pytest.raises(EngineStalledError) as ei:
            list(eng.stream_tokens(req, timeout=0.05))
        err = ei.value
        assert isinstance(err, TimeoutError)  # old catch sites keep working
        assert err.queue_depth >= 1
        assert err.last_step_age_s >= 0.0
        assert 0.0 <= err.kv_utilization <= 1.0
        assert "queue_depth" in str(err)

    def test_pickles_with_diagnosis(self, tiny_params):
        import pickle

        err = EngineStalledError(
            "x", last_step_age_s=1.5, queue_depth=3, kv_utilization=0.5
        )
        back = pickle.loads(pickle.dumps(err))
        assert isinstance(back, EngineStalledError)
        assert back.last_step_age_s == 1.5 and back.queue_depth == 3

    def test_healthy_stream_unaffected(self, tiny_params):
        eng = _engine(tiny_params)
        stop = threading.Event()
        t = threading.Thread(target=eng.run_loop, args=(stop,), daemon=True)
        t.start()
        try:
            req = eng.submit(PROMPT, SamplingParams(max_tokens=8))
            toks = list(eng.stream_tokens(req, timeout=30))
            assert len(toks) == 8
        finally:
            stop.set()
            t.join(timeout=5)
