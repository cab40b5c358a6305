"""The step loop keeps one decode in flight (ISSUE 35): step N+1 is built
and launched from slot state that lives on the device before step N's
tokens are read.  What must not change is every request's tokens and
logprobs; what is new is everything that has to read the device dry first.

The yardstick is the plain float32 reference of the parity tests
(``benchmark/reference/gptj.py``: one full forward pass, no cache, nothing
of ``ray_tpu``'s model code): at each output position the repo's sampler on
the REFERENCE's logits, by the request's own (seed, index, knobs), must
give the engine's token, and the engine's logprob must be the sampler's
there.  Where the reference cannot follow (a weight swap mid-generation
leaves old-weight K/V in the cache) the yardstick is the same engine read
dry after every step: the loop in the order it had before this change.
CPU, tiny widths, ``tp`` 1 and 2 where a case is about the runner.
"""

import functools
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import gptj as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.models.gptj import GPTJConfig, gptj_init  # noqa: E402
from ray_tpu.models.sampling import sample_rows_logprobs  # noqa: E402

TINY = GPTJConfig(vocab_size=128, seq_len=64, d_model=64, n_layers=2, n_heads=4,
                  rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
                  fused_loss=False)
ENGINE = dict(max_slots=3, num_blocks=48, block_size=4, max_blocks_per_seq=12,
              prefill_chunk=8)
TP = [1, pytest.param(2, marks=pytest.mark.skipif(
    len(jax.devices("cpu")) < 2, reason="needs 2 host devices (conftest's XLA_FLAGS)"))]
SAMPLED = dict(temperature=0.8, top_k=12, top_p=0.9)


@functools.lru_cache(maxsize=None)
def _params(seed=0):
    return gptj_init(jax.random.PRNGKey(seed), TINY)


@functools.lru_cache(maxsize=None)
def _shared(tp=1, **kw):
    """One warm engine a ``(tp, geometry)``: a compile is seconds here.  Its
    counters run on from test to test: read them as deltas (``_pipe``)."""
    return _fresh(tp, **kw)


def _fresh(tp=1, params=None, **kw):
    eng = LLMEngine(TINY, params or _params(), EngineConfig(**{**ENGINE, **kw}, tp=tp))
    eng.warmup()
    return eng


def _pipe(eng, before=None):
    p = eng.stats()["pipeline"]
    if before is None:
        return p
    out = {k: p[k] - before[k] for k in ("ahead_steps", "serial_steps", "discarded_tokens")}
    out["drains"] = {k: v - before["drains"].get(k, 0) for k, v in p["drains"].items()
                     if v - before["drains"].get(k, 0)}
    out["uploads"] = {k: v - before["uploads"][k] for k, v in p["uploads"].items()}
    return out


def _prompt(i, n=9):
    return [int(t) for t in np.random.default_rng(100 + i).integers(1, TINY.vocab_size, n)]


def _run(eng, reqs, limit=2000):
    """Step until ``reqs`` are finished and nothing is left in flight (a row
    that ended on a stop token leaves its extra token there)."""
    for _ in range(limit):
        if all(r.finished for r in reqs) and not eng.has_work():
            return
        eng.step()
    raise AssertionError("the engine did not finish its requests")


def _streamed(req):
    """What the request's stream holds: (tokens, the reason after them)."""
    toks, reason = [], None
    while not req.stream.empty():
        kind, val = req.stream.get_nowait()[:2]  # a token carries its emit stamp third
        if kind == "token":
            assert reason is None, "a token after the stream's end"
            toks.append(val)
        else:
            reason = val
    return toks, reason


def _assert_reference(req, params=None, n=None):
    """The first ``n`` (all) of ``req``'s tokens and logprobs are the
    sampler's on the plain reference's logits, index for index."""
    out = req.out[:n]
    assert out, "nothing to compare"
    rows = [len(req.prompt) - 1 + i for i in range(len(out))]
    logits = reference.logits_at(params or _params(), req.prompt + out[:-1], rows,
                                 TINY.n_heads, TINY.rotary_dim)
    p, k = req.params, len(out)
    toks, lps = sample_rows_logprobs(
        logits, np.full(k, p.seed & 0xFFFFFFFF, np.uint32),
        np.arange(req.resumed_from, req.resumed_from + k, dtype=np.int32),
        np.full(k, p.temperature, np.float32), np.full(k, p.top_k, np.int32),
        np.full(k, p.top_p, np.float32))
    assert [int(t) for t in toks] == out, (req.id, out)
    np.testing.assert_allclose(req.out_logprobs[:k], np.asarray(lps), atol=1e-4, rtol=0)


def _assert_clean(eng):
    assert eng.pool.audit()["ok"] and eng.prefix_cache.audit()["ok"]
    assert eng.stats()["retraces"] == 0
    # (``fork`` jits a module-level function: engines of two pool shapes in
    # one process share its cache, so this file cannot hold it to 1)
    sites = {k: v for k, v in eng.device_report()["jit_sites"].items() if k != "fork"}
    assert sites and all(v["cache_size"] == 1 for v in sites.values()), sites
    assert eng._flight is None and eng._first is None and not eng.has_work()


def _mixed(i, max_tokens):
    """Every other request greedy, the rest seeded."""
    knobs = dict(SAMPLED, seed=7 + i) if i % 2 else {}
    return SamplingParams(max_tokens=max_tokens, **knobs)


# -- tokens and logprobs are the reference's ------------------------------------


@pytest.mark.parametrize("tp", TP)
def test_mixed_batch_and_a_queue_longer_than_the_slots(tp):
    eng = _shared(tp)
    before = _pipe(eng)
    reqs = [eng.submit(_prompt(i, 5 + 2 * i), _mixed(i, 6 + i)) for i in range(6)]
    _run(eng, reqs)
    for r in reqs:
        assert len(r.out) == r.params.max_tokens and r.finish_reason == "length"
        assert _streamed(r) == (r.out, "length")
        _assert_reference(r)
    d = _pipe(eng, before)
    assert d["ahead_steps"] > 4 * d["serial_steps"] > 0, d
    # no traffic here ends on a stop token: a row's last token is known
    # when its last decode is built, so nothing is sampled to be dropped
    assert d["discarded_tokens"] == 0 and set(d["drains"]) <= {"empty"}, d
    _assert_clean(eng)


@pytest.mark.parametrize("tp", TP)
def test_arrivals_mid_flight_join_on_the_device(tp):
    eng = _shared(tp)
    reqs = [eng.submit(_prompt(10, 12), _mixed(0, 14))]
    for i in range(1, 5):
        for _ in range(3):  # a decode is in flight whenever the next one arrives
            eng.step()
        assert eng._flight is not None and eng.stats()["pipeline"]["in_flight"] >= 1
        reqs.append(eng.submit(_prompt(10 + i, 3 + 4 * i), _mixed(i, 5 + 2 * i)))
    _run(eng, reqs)
    for r in reqs:
        assert len(r.out) == r.params.max_tokens
        _assert_reference(r)
    _assert_clean(eng)


@pytest.mark.parametrize("max_tokens,prompt_len", [(1, 6), (2, 6), (9, 6), (40, 8)],
                         ids=["one_token", "two_tokens", "nine", "to_the_model_length"])
def test_a_row_whose_last_token_is_known_is_left_out_not_dropped(max_tokens, prompt_len):
    """``max_tokens - 1`` decodes a request, as before: the counts that
    ``test_sampling_sorted.py`` and ``test_paged_kernel_walk.py`` hold."""
    eng = _shared(1)
    assert eng.max_model_len == 48
    before, calls = _pipe(eng), eng.runner.prof.stats()["decode"]["calls"]
    req = eng.submit(_prompt(20, prompt_len), SamplingParams(max_tokens=max_tokens))
    _run(eng, [req])
    assert len(req.out) == max_tokens and req.seq_len <= eng.max_model_len
    _assert_reference(req)
    d = _pipe(eng, before)
    assert eng.runner.prof.stats()["decode"]["calls"] - calls == max_tokens - 1
    assert d["ahead_steps"] + d["serial_steps"] == max_tokens - 1
    assert d["discarded_tokens"] == 0 and d["drains"] == {"empty": 1}, d
    _assert_clean(eng)


# -- a stop token shows one step late -------------------------------------------


@pytest.mark.parametrize("tp", TP)
@pytest.mark.parametrize("company", [False, True], ids=["alone", "beside_a_running_row"])
def test_stop_token_with_a_step_in_flight(tp, company):
    eng = _shared(tp)
    plain = eng.submit(_prompt(30), SamplingParams(max_tokens=12))
    _run(eng, [plain])
    at = next(i for i in range(2, 12) if plain.out[i] not in plain.out[:i])
    before, generated = _pipe(eng), eng.stats()["tokens_generated"]
    other = eng.submit(_prompt(31, 7), _mixed(1, 16)) if company else None
    req = eng.submit(_prompt(30), SamplingParams(max_tokens=12,
                                                 stop_token_ids=(plain.out[at],)))
    _run(eng, [req] + ([other] if company else []))
    assert req.out == plain.out[:at + 1] and req.finish_reason == "stop"
    assert req.out_logprobs == plain.out_logprobs[:at + 1]
    assert _streamed(req) == (req.out, "stop")  # nothing after the stop token
    d = _pipe(eng, before)
    assert d["discarded_tokens"] == 1, d
    n_other = len(other.out) if company else 0
    assert eng.stats()["tokens_generated"] - generated == len(req.out) + n_other
    if company:
        assert len(other.out) == 16
        _assert_reference(other)
    _assert_clean(eng)


# -- what cannot run ahead reads the device dry first -----------------------------


@pytest.mark.parametrize("how", ["cancelled", "deadline"])
def test_cancel_and_deadline_of_a_row_in_flight(how):
    eng = _shared(1)
    before = _pipe(eng)
    doomed = eng.submit(_prompt(40), _mixed(1, 30))
    other = eng.submit(_prompt(41, 6), _mixed(0, 14))
    while len(doomed.out) < 4:
        eng.step()
    assert doomed.id in eng._flight.ids
    n = len(doomed.out)
    if how == "cancelled":
        assert eng.cancel(doomed.id)
    else:
        doomed.deadline = time.time() - 1.0
    eng.step()
    assert doomed.finished and doomed.finish_reason == how
    # the token in flight was read and streamed, not dropped
    assert len(doomed.out) == n + 1 and _streamed(doomed) == (doomed.out, how)
    _assert_reference(doomed)
    _run(eng, [other])
    _assert_reference(other)
    d = _pipe(eng, before)
    assert d["drains"].get(how) == 1 and d["discarded_tokens"] == 0, d
    _assert_clean(eng)


def test_watchdog_reap_drains_too():
    eng = _shared(1)
    before = _pipe(eng)
    req = eng.submit(_prompt(42), SamplingParams(max_tokens=30))
    while len(req.out) < 3:
        eng.step()
    req.cancelled.set()
    with eng._lock:  # the watchdog's locked path: nobody is stepping
        assert eng._reap() == 1
    assert req.finished and _streamed(req) == (req.out, "cancelled") and len(req.out) == 4
    assert _pipe(eng, before)["drains"] == {"cancelled": 1}
    _assert_clean(eng)


@pytest.mark.parametrize("tp", TP)
def test_forced_preemption_replays_exactly(tp):
    # 9 usable blocks of 4 tokens: two rows of 8 + 18 tokens cannot both stay
    eng = _shared(tp, num_blocks=10, max_slots=2, prefix_cache=False)
    before, preempted = _pipe(eng), eng.stats()["preemptions"]
    reqs = [eng.submit(_prompt(50 + i, 8), _mixed(i + 1, 18)) for i in range(2)]
    _run(eng, reqs)
    assert eng.stats()["preemptions"] > preempted
    for r in reqs:
        assert len(r.out) == 18 and _streamed(r) == (r.out, "length")
        _assert_reference(r)
    d = _pipe(eng, before)
    # the row's token in flight was in ``out`` before it was evicted: every
    # eviction met a device read dry
    assert d["drains"].get("preempt", 0) >= 1 and d["discarded_tokens"] == 0, d
    assert eng.pool.audit()["ok"] and eng.stats()["retraces"] == 0
    assert all(v["cache_size"] == 1 for k, v in eng.device_report()["jit_sites"].items()
               if k != "fork")


def _read_dry_every_step(eng, reqs, until=lambda: False):
    """The loop in the order it had before: a step's tokens are on the host
    when it returns."""
    while not (all(r.finished for r in reqs) or until()):
        eng.step()
        eng._drain("oracle")


@pytest.mark.parametrize("tp", TP)
def test_update_weights_with_a_step_in_flight(tp):
    a, b = _params(0), _params(1)
    sp = [_mixed(1, 16), _mixed(0, 12)]
    eng = _fresh(tp, a)
    reqs = [eng.submit(_prompt(60 + i), sp[i]) for i in range(2)]
    while len(reqs[0].out) < 5:
        eng.step()
    assert eng._flight is not None
    assert eng.update_weights(b) == 1
    cut = [len(r.out) for r in reqs]  # sampled under the old weights: all on the host
    assert eng._flight is None and eng.stats()["pipeline"]["drains"]["update_weights"] == 1
    assert cut[0] == 6, "the swap read the decode in flight"
    _run(eng, reqs)
    oracle = _fresh(tp, a)
    want = [oracle.submit(_prompt(60 + i), sp[i]) for i in range(2)]
    _read_dry_every_step(oracle, want, until=lambda: len(want[0].out) >= cut[0])
    assert [len(r.out) for r in want] == cut
    oracle.update_weights(b)
    _read_dry_every_step(oracle, want)
    for got, ref, n in zip(reqs, want, cut):
        assert got.out == ref.out and got.out_logprobs == ref.out_logprobs
        _assert_reference(got, a, n)  # no token of the old version under the new weights
        assert got.out[n:] != _tail_under(a, got, n)
    for e in (eng, oracle):
        assert e.stats()["weights_version"] == 1
        _assert_clean(e)


def _tail_under(params, req, n):
    """What the tokens after the first ``n`` would have been with no swap."""
    eng = _fresh(1, params)
    same = eng.submit(req.prompt, req.params)
    _run(eng, [same])
    assert same.out[:n] == req.out[:n]
    return same.out[n:]


def test_speculation_reads_every_step_at_once():
    eng = _shared(1, spec_k=3)
    before = _pipe(eng)
    periodic = [5, 9, 7, 5, 9, 7, 5, 9]
    reqs = [eng.submit(periodic, SamplingParams(max_tokens=12)),
            eng.submit(_prompt(70), _mixed(1, 10))]
    _run(eng, reqs)
    assert eng.stats()["spec_proposed"] > 0
    for r in reqs:
        _assert_reference(r)
    d = _pipe(eng, before)
    assert d["ahead_steps"] == 0 and d["serial_steps"] > 0 and d["drains"] == {}, d
    _assert_clean(eng)


# -- what a steady step sends and when it waits ----------------------------------


@pytest.mark.parametrize("tp", TP)
def test_steady_steps_send_nothing_and_wait_only_after_their_launches(tp, monkeypatch):
    eng = _shared(tp, block_size=16, max_blocks_per_seq=4, num_blocks=16)
    reqs = [eng.submit(_prompt(80 + i, 6), _mixed(i, 40)) for i in range(2)]
    while not all(len(r.out) >= 2 for r in reqs):
        eng.step()
    order, phase = [], eng._phase
    monkeypatch.setattr(eng, "_phase", lambda key, span=None: (order.append(key), phase(key, span))[1])
    before = _pipe(eng)
    for _ in range(30):
        order.append("step")
        assert eng.step()
    d = _pipe(eng, before)
    assert d["ahead_steps"] == 30 and d["serial_steps"] == 0 and d["drains"] == {}, d
    # two rows of 8 → 38 tokens cross a 16-token block twice each
    assert d["uploads"]["none"] >= 24 and d["uploads"]["full"] == 0, d
    steps = " ".join(order).split("step")[1:]
    assert len(steps) == 30
    for keys in steps:
        keys = keys.split()
        waits = [i for i, k in enumerate(keys) if k in ("decode_fetch", "prefill_sample", "drain")]
        launches = [i for i, k in enumerate(keys) if k.endswith("_launch")]
        assert waits == [keys.index("decode_fetch")] and max(launches) < waits[0], keys
    monkeypatch.undo()
    _run(eng, reqs)
    for r in reqs:
        _assert_reference(r)
    _assert_clean(eng)


@pytest.mark.parametrize("tp", TP)
def test_a_final_chunk_waits_for_nothing_of_its_own(monkeypatch, tp):
    """The steps that carry a prompt's last chunk.  Two launches (the
    tensor-parallel runner): launch the chunk, launch the decode the new
    row joins, THEN read.  One launch (``tp == 1``: the chunk rides the
    decode, ISSUE 47): the first token leaves with that launch's tokens, so
    the row joins the NEXT launch and the token is read where that flight
    is read, after it, never by a wait on the program just launched."""
    eng = _shared(tp)
    first = eng.submit(_prompt(90), _mixed(0, 30))
    while len(first.out) < 3:
        eng.step()
    order, phase = [], eng._phase
    monkeypatch.setattr(eng, "_phase", lambda key, span=None: (order.append(key), phase(key, span))[1])
    late = eng.submit(_prompt(91, 13), _mixed(1, 6))  # two chunks of 8
    while not late.out:
        order.append("step")
        eng.step()
    assert late.id in eng._flight.ids and len(late.out) == 1  # its decode is launched
    steps = [[k for k in keys.split() if k.endswith(("_launch", "_fetch", "_sample", "emit"))]
             for keys in " ".join(order).split("step")[1:]]
    if tp > 1:
        # the decode's tokens are read and out before the wait for the chunk's
        assert steps[-1] == ["prefill_launch", "decode_launch", "decode_fetch", "emit",
                             "prefill_sample", "emit"], steps
    else:
        # one launch a step, billed once; the step after the final chunk's
        # reads the flight it rode, then its token (already there)
        assert steps[-2:] == [["prefill_launch", "decode_fetch", "emit"],
                              ["decode_launch", "decode_fetch", "emit", "prefill_sample",
                               "emit"]], steps
    monkeypatch.undo()
    _run(eng, [first, late])
    _assert_reference(first)
    _assert_reference(late)
    _assert_clean(eng)


def test_decode_fetch_spans_end_where_a_decode_was_read(monkeypatch):
    """The device trace's clock is set by ``llm.step.decode_fetch`` ends
    (``benchmark/layer_metrics/_program_spans``): the first decode after a
    pause has no decode to read, so its wait for the first token is a
    ``prefill_sample`` alone and no ``decode_fetch`` span opens."""
    eng = _shared(1)
    assert not eng.has_work()
    order, phase = [], eng._phase
    monkeypatch.setattr(eng, "_phase", lambda key, span=None: (order.append(key), phase(key, span))[1])
    req = eng.submit(_prompt(92, 6), SamplingParams(max_tokens=4))
    while not req.finished:
        order.append("step")
        eng.step()
    steps = [k.split() for k in " ".join(order).split("step")[1:]]
    waits = [[k for k in keys if k in ("decode_fetch", "prefill_sample", "drain")]
             for keys in steps]
    # chunk + first decode | decodes 2, 3 launched, 1, 2 read | batch empty
    assert waits == [["prefill_sample"], ["decode_fetch"], ["decode_fetch"], ["drain"]], waits
    _assert_reference(req)
    _assert_clean(eng)


# -- the loop's ends ----------------------------------------------------------------


def test_has_work_while_only_a_dropped_token_is_in_flight():
    eng = _shared(1)
    plain = eng.submit(_prompt(95), SamplingParams(max_tokens=6))
    _run(eng, [plain])
    req = eng.submit(_prompt(95), SamplingParams(max_tokens=6,
                                                 stop_token_ids=(plain.out[0],)))
    while not req.finished:
        eng.step()
    # the request ended on its FIRST token, read beside the decode it had joined
    assert req.out == plain.out[:1] and not eng.scheduler.has_work()
    assert eng._flight is not None and eng.has_work()
    assert eng.stats()["pipeline"]["in_flight"] == 1
    assert eng.step() and not eng.has_work() and not eng.step()
    _assert_clean(eng)


def test_the_loop_reads_the_device_dry_when_it_stops():
    eng = _shared(1)
    stop = threading.Event()
    loop = threading.Thread(target=eng.run_loop, args=(stop,))
    loop.start()
    try:
        req = eng.submit(_prompt(96), _mixed(1, 38))
        got = []
        for tok in eng.stream_tokens(req, timeout=60):
            got.append(tok)
            if len(got) == 5:
                break
    finally:
        stop.set()
        loop.join(30)
    assert not loop.is_alive() and eng._flight is None
    n = len(req.out)
    assert n >= 5 and got == req.out[:5]
    assert _streamed(req) == (req.out[5:], None)  # every sampled token reached the stream
    _run(eng, [req])  # and the request goes on from there, token for token
    assert len(req.out) == 38
    _assert_reference(req)
    _assert_clean(eng)
