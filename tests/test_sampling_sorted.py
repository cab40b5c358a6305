"""The sampler samples in sorted order (PR 27): tokens and logprobs held
equal to the formulation it replaced, kept HERE as the oracle — a full
descending sort, a vocabulary-wide gather, the filter, a scatter back to
vocabulary order, ``jax.random.categorical`` over the scattered row, one
row at a time under ``vmap``.  The oracle is a copy, not an import: the
program may never grow that path back without this file noticing."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.model_runner import _sample_rows, _verify_rows
from ray_tpu.models.sampling import sample_tokens_logprobs, token_logprobs

V = 3000
ROWS = 16
NEG = -1e30


# -- the oracle: models/sampling.py and model_runner.py as of PR 26 ------------


def _oracle_filtered(logits, temp, kk, pp):
    b, v = logits.shape
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)
    sorted_scaled = jnp.take_along_axis(scaled, order, axis=-1)
    ranks = jnp.arange(v)[None, :]
    probs = jax.nn.softmax(sorted_scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (kk[:, None] <= 0) | (ranks < kk[:, None])
    keep &= (cum - probs) < pp[:, None]
    masked_sorted = jnp.where(keep, sorted_scaled, NEG)
    return (
        jnp.full_like(scaled, NEG).at[jnp.arange(b)[:, None], order].set(masked_sorted)
    )


def _oracle_sample(logits, key, temp, kk, pp):
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _oracle_filtered(logits, temp, kk, pp)
    keys = jax.random.split(key, logits.shape[0])
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    tok = jnp.where(temp > 0.0, sampled, greedy)
    idx = tok[:, None]
    lp_s = jnp.take_along_axis(jax.nn.log_softmax(masked, axis=-1), idx, axis=-1)[:, 0]
    lp_g = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), idx, axis=-1)[:, 0]
    return tok, jnp.where(temp > 0.0, lp_s, lp_g)


def _oracle_one(lg, key, t, k, p):
    tok, lp = _oracle_sample(lg[None, :], key, t[None], k[None], p[None])
    return tok[0], lp[0]


@jax.jit
def _oracle_sample_rows(logits, seeds, counters, temp, top_k, top_p):
    keys = jax.vmap(lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(
        seeds, counters
    )
    return jax.vmap(_oracle_one)(logits, keys, temp, top_k, top_p)


@jax.jit
def _oracle_verify_rows(logits, draft, seeds, counters, temp, top_k, top_p):
    def window(lg, dr, seed, counter, t, k, p):
        w = lg.shape[0]
        base = jax.random.PRNGKey(seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, counter + i))(
            jnp.arange(w, dtype=jnp.int32)
        )
        bc = lambda x: jnp.broadcast_to(x, (w,))  # noqa: E731
        out, logp = jax.vmap(_oracle_one)(lg, keys, bc(t), bc(k), bc(p))
        accept = dr == out[: w - 1]
        n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32))).astype(jnp.int32)
        return n_acc, out, logp

    return jax.vmap(window)(logits, draft, seeds, counters, temp, top_k, top_p)


# -- operands -------------------------------------------------------------------


def _logits(batch_seed, rows=ROWS, v=V, bf16=False):
    rng = np.random.RandomState(batch_seed)
    lg = rng.randn(rows, v).astype(np.float32) * rng.uniform(0.5, 4.0, (rows, 1))
    if bf16:
        # what the lm_head hands the sampler: bf16 values, so that rows tie
        lg = np.asarray(jnp.asarray(lg).astype(jnp.bfloat16).astype(jnp.float32))
    return jnp.asarray(lg, jnp.float32)


def _ids(batch_seed, rows=ROWS):
    rng = np.random.RandomState(1000 + batch_seed)
    return (
        jnp.asarray(rng.randint(0, 2**32, rows, dtype=np.uint64).astype(np.uint32)),
        jnp.asarray(rng.randint(0, 400, rows), jnp.int32),
    )


def _knobs(temp, top_k, top_p, rows=ROWS):
    full = lambda x, dt: jnp.broadcast_to(jnp.asarray(x, dt), (rows,))  # noqa: E731
    return full(temp, jnp.float32), full(top_k, jnp.int32), full(top_p, jnp.float32)


_MIXED = (
    [0.0, 0.8, 1.0, 1.5] * 4,
    [0, 40, 1, 5, V, 0, 40, 3] * 2,
    [1.0, 0.95, 0.5, 0.1, 1.0, 0.9, 0.95, 0.3] * 2,
)

#: name -> (temperature, top_k, top_p), scalars or one value a row
CASES = {
    "greedy": (0.0, 0, 1.0),
    "greedy_knobs_ignored": (0.0, 40, 0.5),
    "temperature_only": (0.8, 0, 1.0),
    "temperature_hot": (1.5, 0, 1.0),
    "top_k_1": (1.0, 1, 1.0),
    "top_k_40": (0.8, 40, 1.0),
    "top_k_V": (1.0, V, 1.0),
    "top_p_0.1": (1.0, 0, 0.1),
    "top_p_0.5": (0.8, 0, 0.5),
    "top_p_0.95": (0.8, 0, 0.95),
    "chat_cell": (0.8, 40, 0.95),
    "tight_both": (1.5, 5, 0.5),
    "mixed_batch": _MIXED,
}

_sample_rows_jit = jax.jit(_sample_rows)
_verify_rows_jit = jax.jit(_verify_rows)


def _assert_same(got, want):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=1e-5, rtol=0)


# -- (a) token for token against the oracle ------------------------------------


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16_ties"])
@pytest.mark.parametrize("case", list(CASES))
def test_decode_sampler_equals_full_sort_oracle(case, bf16):
    knobs = _knobs(*CASES[case])
    for batch_seed in range(3):
        logits = _logits(batch_seed, bf16=bf16)
        seeds, counters = _ids(batch_seed)
        _assert_same(
            _sample_rows_jit(logits, seeds, counters, *knobs),
            _oracle_sample_rows(logits, seeds, counters, *knobs),
        )


@pytest.mark.parametrize("case", ["greedy", "temperature_only", "chat_cell", "top_p_0.1"])
def test_one_row_the_prefill_sampler_shape(case):
    """The sampler at one row: what the prefill program runs on a prompt's last chunk
    (``model_runner._prefill_sample``)."""
    knobs = _knobs(*CASES[case], rows=1)
    for batch_seed in range(4):
        logits = _logits(batch_seed, rows=1, bf16=True)
        seeds, counters = _ids(batch_seed, rows=1)
        _assert_same(
            _sample_rows_jit(logits, seeds, counters, *knobs),
            _oracle_sample_rows(logits, seeds, counters, *knobs),
        )


@pytest.mark.parametrize("case", ["temperature_only", "chat_cell", "mixed_batch"])
def test_sample_tokens_one_key_a_batch_equals_oracle(case):
    """``sample_tokens_logprobs`` (models' ``generate``): rows draw from the splits of ONE key."""
    knobs = _knobs(*CASES[case])
    for batch_seed in range(3):
        logits, key = _logits(batch_seed, bf16=True), jax.random.PRNGKey(batch_seed)
        _assert_same(
            sample_tokens_logprobs(logits, key, *knobs), _oracle_sample(logits, key, *knobs)
        )


@pytest.mark.parametrize("case", ["greedy", "chat_cell", "mixed_batch"])
def test_verify_equals_oracle(case):
    slots, w = 4, 4
    temp, top_k, top_p = (k[:slots] for k in _knobs(*CASES[case]))
    for batch_seed in range(2):
        logits = _logits(batch_seed, rows=slots * w, bf16=True).reshape(slots, w, V)
        seeds, counters = _ids(batch_seed, rows=slots)
        # drafts that match, so that acceptance counts are not all zero
        want = _oracle_verify_rows(
            logits, jnp.zeros((slots, w - 1), jnp.int32), seeds, counters, temp, top_k, top_p
        )
        draft = jnp.asarray(want[1])[:, : w - 1].at[1, 1].add(1).at[2, 0].add(1)
        want = _oracle_verify_rows(logits, draft, seeds, counters, temp, top_k, top_p)
        got = _verify_rows_jit(logits, draft, seeds, counters, temp, top_k, top_p)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert list(np.asarray(got[0])) == [w - 1, 1, 0, w - 1]
        _assert_same(got[1:], want[1:])


# -- (b) decode and verify draw the same token at the same (seed, index) -------


def test_decode_and_verify_agree_on_seed_and_index():
    slots, w = 4, 3
    temp, top_k, top_p = _knobs([0.8, 1.0, 1.5, 0.8], [40, 0, 5, 0], [0.95, 1.0, 0.9, 0.5], rows=slots)
    logits = _logits(7, rows=slots * w).reshape(slots, w, V)
    seeds, counters = _ids(7, rows=slots)
    _, out, logp = _verify_rows_jit(
        logits, jnp.zeros((slots, w - 1), jnp.int32), seeds, counters, temp, top_k, top_p
    )
    for i in range(w):
        tok, lp = _sample_rows_jit(logits[:, i], seeds, counters + i, temp, top_k, top_p)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(out[:, i]))
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(logp[:, i]))
    # the index, not the slot or the batch, keys the draw: a row alone draws the same
    tok1, _ = _sample_rows_jit(
        logits[2:3, 1], seeds[2:3], counters[2:3] + 1, temp[2:3], top_k[2:3], top_p[2:3]
    )
    assert int(tok1[0]) == int(out[2, 1])


# -- (c) the program: no scatter, one sort, and that sort inside the branch ----


def _primitives(jaxpr, inside_cond=False):
    """(primitive name, inside a cond's branch) of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub, inside_cond or eqn.primitive.name == "cond")


@pytest.mark.parametrize("step", ["decode", "verify"])
def test_program_holds_one_sort_inside_the_conditional_and_no_scatter(step):
    rows, v = 8, 512
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    ids = (jax.ShapeDtypeStruct((rows,), jnp.uint32), i32(rows), f32(rows), i32(rows), f32(rows))
    if step == "decode":
        fn, args = _sample_rows, (f32(rows, v), *ids)
    else:
        fn, args = _verify_rows, (f32(rows, 3, v), i32(rows, 2), *ids)
    prims = list(_primitives(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [n for n, _ in prims]
    assert names.count("cond") == 1
    assert [inside for n, inside in prims if n == "sort"] == [True]
    assert not any(n.startswith("scatter") for n in names)
    # nothing indexes the vocabulary element by element either
    assert "gather" not in names and "dynamic_slice" not in names
    # the greedy reductions stand outside the branch: they serve both
    assert ("argmax", False) in prims

    text = jax.jit(fn).lower(*args).as_text()
    assert len(re.findall(r"stablehlo\.sort", text)) == 1
    assert "scatter" not in text and "stablehlo.gather" not in text
    assert len(re.findall(r"stablehlo\.(case|if)\b", text)) == 1


# -- (d) the branch changes nothing a row sees ---------------------------------


def test_greedy_rows_identical_in_greedy_and_mixed_batches():
    logits = _logits(3, bf16=True)
    seeds, counters = _ids(3)
    all_greedy = _sample_rows_jit(logits, seeds, counters, *_knobs(0.0, 0, 1.0))
    temp, top_k, top_p = _knobs(*_MIXED)
    mixed = _sample_rows_jit(logits, seeds, counters, temp, top_k, top_p)
    rows = np.asarray(temp) <= 0.0
    assert rows.sum() == 4
    np.testing.assert_array_equal(np.asarray(all_greedy[0])[rows], np.asarray(mixed[0])[rows])
    np.testing.assert_array_equal(np.asarray(all_greedy[1])[rows], np.asarray(mixed[1])[rows])
    np.testing.assert_array_equal(
        np.asarray(all_greedy[0]), np.argmax(np.asarray(logits), axis=-1)
    )
    # a sampled row is its own too: the same draw among other neighbours
    alone = _sample_rows_jit(logits[1:2], seeds[1:2], counters[1:2], temp[1:2], top_k[1:2], top_p[1:2])
    assert int(alone[0][0]) == int(mixed[0][1]) and float(alone[1][0]) == float(mixed[1][1])


# -- the scoring entry keeps the vocabulary-order filter: held to the sampler --


@pytest.mark.parametrize("case", ["greedy", "chat_cell", "top_p_0.5", "mixed_batch"])
def test_token_logprobs_scores_the_drawn_token_as_the_sampler_did(case):
    knobs = _knobs(*CASES[case])
    logits = _logits(5, bf16=True)
    tok, lp = sample_tokens_logprobs(logits, jax.random.PRNGKey(5), *knobs)
    np.testing.assert_allclose(
        np.asarray(token_logprobs(logits, tok, *knobs)), np.asarray(lp), atol=1e-5, rtol=0
    )


# -- the counter: which branch the batches sent took ---------------------------


def test_engine_counts_greedy_and_sorted_steps():
    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models.gptj import GPTJConfig, gptj_init

    cfg = GPTJConfig(
        vocab_size=128, seq_len=64, d_model=32, n_layers=2, n_heads=2, rotary_dim=8,
        dtype="float32", remat=False, attn_impl="xla", fused_loss=False,
    )
    eng = LLMEngine(
        cfg, gptj_init(jax.random.PRNGKey(0), cfg),
        EngineConfig(max_slots=2, num_blocks=24, block_size=4, max_blocks_per_seq=10, prefill_chunk=8),
    )
    assert eng.stats()["sampler"] == {"greedy_steps": 0, "sorted_steps": 0}
    prompt = [5, 9, 7, 5, 9, 7, 5, 9]
    # the first token comes from the prompt's last chunk: 5 tokens are 4 decodes
    eng.generate(prompt, SamplingParams(max_tokens=5))
    assert eng.stats()["sampler"] == {"greedy_steps": 4, "sorted_steps": 0}
    eng.generate(prompt, SamplingParams(max_tokens=4, temperature=0.8, top_k=5, seed=3))
    assert eng.stats()["sampler"] == {"greedy_steps": 4, "sorted_steps": 3}
    # one sampled row makes the batch a sorted one, for as long as it lives
    long_greedy = eng.submit(prompt, SamplingParams(max_tokens=9))
    short_sampled = eng.submit(prompt, SamplingParams(max_tokens=3, temperature=1.0, seed=1))
    while not (long_greedy.finished and short_sampled.finished):
        eng.step()
    s = eng.stats()["sampler"]
    assert s["sorted_steps"] == 3 + 2 and s["greedy_steps"] > 4
    assert eng.stats()["retraces"] == 0
