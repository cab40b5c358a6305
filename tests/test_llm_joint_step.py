"""A step that carries a chunk is ONE program (ISSUE 47): the slots' rows
and the chunk's rows go through ``PagedModelRunner``'s layer loop together
(``_prefill_with_slots_impl``), and ``engine.step`` launches it where it
used to launch a prefill program and then a decode program.

Three things are held here, on a CPU at the tests' sizes:

* the PROGRAM against the two programs run in today's order (the chunk,
  then the decode) on the same pools and operands: the same pools, the same
  carry, the same tokens (greedy and sampled), logprobs within the decode /
  prefill parity tests' tolerance; ``gptj`` and ``gpt``; a first, a middle
  and a FINAL chunk; live, dead and just-joined (``PATCH_JOIN``) slots;
* the ENGINE: a run of mixed prompts gives every request the tokens of the
  same run with the joint program withheld (a runner that does not offer
  it), ``stats()["pipeline"]`` says how often it engaged, and a runner that
  has no such program (tensor-parallel, hooks body) never counts a chunk;
* ``warmup()`` compiles it, so no later step does.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import events as _events
from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm.model_runner import (
    PATCH_JOIN,
    PATCH_KEEP,
    PATCH_SET,
    PagedModelRunner,
    pack_knobs,
)
from ray_tpu.llm.multichip import TensorParallelPagedModelRunner
from ray_tpu.llm.scheduler import SamplingParams
from ray_tpu.models.gpt import GPTConfig, gpt_init
from ray_tpu.models.gptj import GPTJConfig, gptj_init

L, NB, BS, TMAX, HEADS, HD = 2, 64, 4, 8, 4, 16
SLOTS, CHUNK, VOCAB = 4, 8, 96
ARCHS = {
    "gpt": (
        GPTConfig(vocab_size=VOCAB, d_model=HEADS * HD, n_layers=L, n_heads=HEADS,
                  seq_len=64, dtype="float32"),
        gpt_init,
    ),
    "gptj": (
        GPTJConfig(vocab_size=VOCAB, seq_len=64, d_model=HEADS * HD, n_layers=L,
                   n_heads=HEADS, rotary_dim=8, dtype="float32", remat=False,
                   attn_impl="xla", fused_loss=False),
        gptj_init,
    ),
}
#: (start, n_valid): a first chunk, one in the middle of a prompt, one that
#: starts inside a block (a prefix hit that diverged there), and a FINAL
#: chunk (tail-padded: its last valid row's logits seed generation)
CHUNKS = {"first": (0, CHUNK), "middle": (CHUNK, CHUNK), "mid_block": (5, CHUNK),
          "final": (2 * CHUNK, 3)}
SAMPLED = dict(temp=0.8, top_k=12, top_p=0.9)


@functools.lru_cache(maxsize=None)
def _runner(arch):
    cfg, init = ARCHS[arch]
    return PagedModelRunner(cfg, init(jax.random.PRNGKey(0), cfg), BS, "xla")


def _pools():
    shape = (L, NB, HEADS, BS, HD)
    return (jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32),
            jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32))


def _operands(chunk_case, sampled):
    """(the decode's operands, the chunk's operands), each after the pools.
    Slot 0 goes on from the carry (``PATCH_KEEP``), slot 1 is set from the
    host, slot 2 JOINS with the token an earlier final chunk left on the
    device, slot 3 is dead (position 0, an all-trash table, ``live`` 0)."""
    rng = np.random.default_rng(11)
    blocks = rng.permutation(np.arange(1, NB)).astype(np.int32)
    tables = blocks[: SLOTS * TMAX].reshape(SLOTS, TMAX).copy()
    tables[3] = 0
    table = blocks[SLOTS * TMAX : SLOTS * TMAX + TMAX]  # the chunk's own blocks
    carry = np.array([[17, 0, 0, 0], [9, 0, 0, 0], [4, 0, 0, 0]], np.int32)
    patch = np.array([[PATCH_KEEP, 0, 0, 0], [PATCH_SET, 33, 14, 2],
                      [PATCH_JOIN, 0, 21, 1], [PATCH_SET, 0, 0, 0]], np.int32)
    first_tok = np.array([71], np.int32)
    live = np.array([1, 1, 1, 0])
    temp = np.where(live > 0, SAMPLED["temp"] if sampled else 0.0, 0.0)
    temp[1] = 0.0  # a greedy row beside sampled ones
    knobs = pack_knobs(live, temp, np.full(SLOTS, SAMPLED["top_k"]),
                       np.full(SLOTS, SAMPLED["top_p"]), np.arange(SLOTS) + 5)
    start, n_valid = CHUNKS[chunk_case]
    tokens = np.zeros(CHUNK, np.int32)
    tokens[:n_valid] = rng.integers(1, VOCAB, n_valid)
    sampling = pack_knobs(0, SAMPLED["temp"] if sampled else 0.0, SAMPLED["top_k"],
                          SAMPLED["top_p"], 1234)
    return ((carry, first_tok, patch, tables, knobs),
            (tokens, np.int32(start), np.int32(n_valid), table, sampling))


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("chunk_case", list(CHUNKS))
@pytest.mark.parametrize("arch", list(ARCHS))
def test_joint_program_is_the_two_programs(arch, chunk_case, sampled):
    runner = _runner(arch)
    dec, pre = _operands(chunk_case, sampled)
    k, v = _pools()
    k, v, _logits, tok, tok_logp = runner._prefill(runner.params, k, v, *pre)
    k, v, carry, nxt, logp = runner._decode(runner.params, k, v, *dec)
    want = dict(k=k, v=v, carry=carry, nxt=nxt, logp=logp, tok=tok, tok_logp=tok_logp)
    got = dict(zip(
        ("k", "v", "carry", "nxt", "logp", "tok", "tok_logp"),
        runner._prefill_with_slots(runner.params, *_pools(), *dec, *pre),
    ))
    # bit for bit, the trash block too: on a CPU a row's products do not
    # depend on how many rows stand beside it (on the chip they may, in the
    # last bf16 place: the cell's reference comparison is the judge there)
    for name in ("k", "v", "carry", "nxt", "tok"):
        np.testing.assert_array_equal(
            np.asarray(got[name]), np.asarray(want[name]), err_msg=name)
    for name in ("logp", "tok_logp"):
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(want[name]), rtol=1e-3, atol=1e-3,
            err_msg=name)
    # what the carry says of the slots: the sampled token, one position and
    # one counter on for the live ones; the dead one stays (0, 0, 0)
    assert np.asarray(got["carry"])[:, 3].tolist() == [0, 0, 0]
    assert np.asarray(got["carry"])[1:, 2].tolist() == [22, 2]


def test_joint_program_is_named_for_the_trace_readers():
    """The benchmark's readers match program names: this one must read as
    ``prefill`` and never as ``decode`` or ``verify``."""
    name = _runner("gptj")._prefill_with_slots.__name__
    assert "prefill" in name and "decode" not in name and "verify" not in name


def test_only_the_one_chip_string_runner_offers_it():
    from ray_tpu.llm.state_runner import HybridModelRunner, StateModelRunner

    assert callable(PagedModelRunner.prefill_with_slots)
    assert TensorParallelPagedModelRunner.prefill_with_slots is None
    for cls in (StateModelRunner, HybridModelRunner):
        assert getattr(cls, "prefill_with_slots", None) is None


# -- the engine ---------------------------------------------------------------

TINY = GPTJConfig(vocab_size=128, seq_len=96, d_model=64, n_layers=2, n_heads=4,
                  rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
                  fused_loss=False)
ENGINE = dict(max_slots=3, num_blocks=64, block_size=4, max_blocks_per_seq=16,
              prefill_chunk=8)


@functools.lru_cache(maxsize=None)
def _tiny_params():
    return gptj_init(jax.random.PRNGKey(0), TINY)


def _engine(joint=True, **kw):
    eng = LLMEngine(TINY, _tiny_params(), EngineConfig(**{**ENGINE, **kw}))
    if not joint:
        eng.runner.prefill_with_slots = None  # a runner that does not offer it
    eng.warmup()
    return eng


def _prompt(i, n):
    return [int(t) for t in np.random.default_rng(300 + i).integers(1, TINY.vocab_size, n)]


def _mixed(eng):
    """Prompts of one chunk and of several, more requests than slots, greedy
    and seeded rows mixed, submitted a few steps apart so that chunks meet
    running decodes.  Returns every request's (tokens, logprobs)."""
    lens = [5, 20, 9, 31, 8, 17, 26]
    reqs = []
    for i, n in enumerate(lens):
        p = SamplingParams(max_tokens=6 + i % 4) if i % 2 == 0 else SamplingParams(
            max_tokens=6 + i % 4, temperature=0.8, top_k=12, top_p=0.9, seed=40 + i)
        reqs.append(eng.submit(_prompt(i, n), p))
        for _ in range(2):
            eng.step()
    for _ in range(3000):
        if not eng.has_work():
            break
        eng.step()
    assert not eng.has_work() and all(r.finished for r in reqs)
    assert eng.pool.audit()["ok"]
    assert eng.prefix_cache is None or eng.prefix_cache.audit()["ok"]
    return [(list(r.out), list(r.out_logprobs)) for r in reqs]


@pytest.mark.parametrize("prefix_cache", [True, False], ids=["cache", "nocache"])
def test_engine_tokens_are_those_of_two_launches(prefix_cache):
    with_joint, without = _engine(True, prefix_cache=prefix_cache), _engine(
        False, prefix_cache=prefix_cache)
    base = with_joint.stats()["pipeline"]
    got, want = _mixed(with_joint), _mixed(without)
    for i, ((toks, logps), (wtoks, wlogps)) in enumerate(zip(got, want)):
        assert toks == wtoks, f"request {i}"
        np.testing.assert_allclose(logps, wlogps, rtol=1e-3, atol=1e-3)
    pipe = with_joint.stats()["pipeline"]
    # chunks met running decodes and rode them; the first request's chunk
    # found an empty batch and went alone
    assert pipe["joint_steps"] - base["joint_steps"] > 0
    assert pipe["lone_chunks"] - base["lone_chunks"] > 0
    # a runner that offers nothing counts nothing: every chunk went alone
    off = without.stats()["pipeline"]
    assert off["joint_steps"] == 0 and off["lone_chunks"] == 0
    assert with_joint.stats()["retraces"] == 0


def test_first_token_of_a_final_chunk_joins_the_next_launch():
    """A final chunk that rode a decode leaves its first token on the
    device beside that decode's tokens: the row decodes from the NEXT
    launch (``PATCH_JOIN``) and the token is read where that flight is,
    one step later, never by waiting on the program just launched."""
    eng = _engine(True, prefix_cache=False)
    a = eng.submit(_prompt(0, 5), SamplingParams(max_tokens=40))
    while len(a.out) < 2:
        eng.step()
    b = eng.submit(_prompt(1, 6), SamplingParams(max_tokens=4))  # one chunk: final
    before = eng.stats()["pipeline"]["joint_steps"]
    eng.step()  # admits b, launches its chunk WITH a's decode
    assert eng.stats()["pipeline"]["joint_steps"] == before + 1
    assert b.state == "running" and b.out == [] and eng._first[0] is b
    flight = eng._flight
    assert b.id not in flight.ids  # it could not decode in that pass
    eng.step()  # b joins this launch; its first token is read with the flight before
    assert len(b.out) == 1 and eng._first is None and b.id in eng._flight.ids
    while eng.has_work():
        eng.step()
    want = _engine(False, prefix_cache=False)
    assert b.out == want.generate(_prompt(1, 6), SamplingParams(max_tokens=4))


@pytest.mark.parametrize("kind", ["tp", "hooks"])
def test_other_runners_keep_two_launches(kind):
    if kind == "tp":
        if len(jax.devices("cpu")) < 2:
            pytest.skip("needs 2 host devices (conftest's XLA_FLAGS)")
        eng = LLMEngine(TINY, _tiny_params(), EngineConfig(**ENGINE, tp=2))
    else:
        from ray_tpu.models.brumby import BrumbyConfig, brumby_init

        cfg = BrumbyConfig(vocab_size=64, seq_len=96, d_model=32, n_layers=2,
                           n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                           dtype="float32")
        eng = LLMEngine(cfg, brumby_init(jax.random.PRNGKey(0), cfg), EngineConfig(
            max_slots=3, prefill_chunk=8, prefix_cache=False))
    reqs = [eng.submit([1 + i, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11][: 5 + 3 * i],
                       SamplingParams(max_tokens=5)) for i in range(3)]
    while eng.has_work():
        eng.step()
    assert all(r.finished and len(r.out) == 5 for r in reqs)
    pipe = eng.stats()["pipeline"]
    assert pipe["joint_steps"] == 0 and pipe["lone_chunks"] == 0


def test_warmup_compiles_the_joint_program(monkeypatch):
    seen = []
    eng = LLMEngine(TINY, _tiny_params(), EngineConfig(**ENGINE))
    eng.warmup()
    assert "prefill_with_slots" in eng.runner.first_call_s
    compiled = set(eng.runner._compiled)
    orig = _events.record

    def record(kind, **fields):
        if kind == "llm.compile":
            seen.append(fields)
        return orig(kind, **fields)

    monkeypatch.setattr(_events, "record", record)
    _mixed(eng)
    assert not seen and eng.runner._compiled == compiled
    assert eng.stats()["pipeline"]["joint_steps"] > 0
    assert eng.stats()["retraces"] == 0
    sites = eng.runner.prof.stats()
    assert sites["prefill_with_slots"]["retraces"] == 0


def test_every_chunk_rides_beside_a_full_batch():
    """No row count keeps a chunk off the decode: with every other slot
    decoding, all three chunks of a prompt ride, and its tokens are those
    of a run alone."""
    eng = _engine(True, prefix_cache=False)
    rows = [eng.submit(_prompt(i, 5 + i), SamplingParams(max_tokens=40))
            for i in range(ENGINE["max_slots"] - 1)]
    while any(len(r.out) < 2 for r in rows):
        eng.step()
    base = eng.stats()["pipeline"]
    c = eng.submit(_prompt(7, 20), SamplingParams(max_tokens=4))  # three chunks
    while not c.finished:
        eng.step()
    pipe = eng.stats()["pipeline"]
    assert pipe["joint_steps"] == base["joint_steps"] + 3
    assert pipe["lone_chunks"] == base["lone_chunks"]
    assert c.out == _engine(True, prefix_cache=False).generate(
        _prompt(7, 20), SamplingParams(max_tokens=4))


def test_a_lone_chunk_reads_the_first_token_still_on_the_device():
    """A final chunk rode the batch's LAST decode and its own row wants one
    token only: the next step has a chunk due and nothing to decode, so the
    chunk goes alone, and the first token left on the device a step ago is
    read before the chunk's takes its place (drain reason ``lone_chunk``)."""
    eng = _engine(True, prefix_cache=False)
    a = eng.submit(_prompt(0, 5), SamplingParams(max_tokens=3))
    eng.step()  # a's chunk alone, its second token launched
    x = eng.submit(_prompt(1, 6), SamplingParams(max_tokens=1))
    y = eng.submit(_prompt(2, 12), SamplingParams(max_tokens=3))  # two chunks
    base = eng.stats()["pipeline"]
    eng.step()  # x's final chunk rides a's last decode
    assert eng.stats()["pipeline"]["joint_steps"] == base["joint_steps"] + 1
    assert eng._first[0] is x and not eng._decode_rows()
    eng.step()  # y's first chunk: alone, after the device is read dry
    pipe = eng.stats()["pipeline"]
    assert pipe["drains"].get("lone_chunk") == 1
    assert pipe["lone_chunks"] == base["lone_chunks"] + 1
    assert a.finished and len(a.out) == 3 and x.finished and len(x.out) == 1
    while eng.has_work():
        eng.step()
    want = _engine(False, prefix_cache=False)
    for i, (r, n, m) in enumerate(((a, 5, 3), (x, 6, 1), (y, 12, 3))):
        assert r.out == want.generate(_prompt(i, n), SamplingParams(max_tokens=m))
