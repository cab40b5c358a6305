"""The sampler samples in sorted order (PR 27): tokens and logprobs held
equal to the formulation it replaced, kept HERE as the oracle — a full
descending sort, a vocabulary-wide gather, the filter, a scatter back to
vocabulary order, ``jax.random.categorical`` over the scattered row, one
row at a time under ``vmap``.  The oracle is a copy, not an import: the
program may never grow that path back without this file noticing.

Since PR 46 only the rows that sample are sorted, packed into the narrowest
width of ``sort_ladder`` that holds them.  The FULL-WIDTH sampler it replaced
(every row sorted under one ``cond``) is kept here as a second reference, and
every case is held to it bit for bit: which rows stand beside a row, and how
many, changes nothing it draws."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.model_runner import _sample_rows, _verify_rows
from ray_tpu.models.sampling import (
    _NEG_INF,
    _keep_sorted,
    _request_keys,
    sample_tokens_logprobs,
    sort_ladder,
    sort_rung,
    token_logprobs,
)

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


# -- the full-width sampler: models/sampling.py::_draw_rows as of PR 45 ---------


@jax.jit
def _full_width_rows(logits, seeds, counters, temp, kk, pp):
    """Every row sorted whenever any row samples: the program PR 46 replaced.  Its
    parts that stayed (``_keep_sorted``, ``_request_keys``) are the program's own."""
    logits, keys = logits.astype(jnp.float32), _request_keys(seeds, counters)
    b, v = logits.shape
    sampled_row = temp > 0.0
    top = jnp.max(logits, axis=-1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_lp = -jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))

    def sorted_draw():
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        noise = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
        ids = jax.lax.broadcasted_iota(jnp.int32, (b, v), 1)
        neg, order, noise = jax.lax.sort(
            (-scaled, ids, noise), dimension=1, is_stable=True, num_keys=1
        )
        masked = jnp.where(_keep_sorted(-neg, kk, pp), -neg, _NEG_INF)
        z = masked + noise
        tok = jnp.min(jnp.where(z == jnp.max(z, axis=-1, keepdims=True), order, v), axis=-1)
        chosen = jnp.max(jnp.where(order == tok[:, None], masked, -jnp.inf), axis=-1)
        norm = jnp.log(jnp.sum(jnp.exp(masked - masked[:, :1]), axis=-1))
        return tok, (chosen - masked[:, 0]) - norm

    tok, lp = jax.lax.cond(jnp.any(sampled_row), sorted_draw, lambda: (greedy, greedy_lp))
    return jnp.where(sampled_row, tok, greedy), jnp.where(sampled_row, lp, greedy_lp)


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

#: rows of a 32-row batch that sample, the others greedy or empty: none, one, a rung's
#: width, a rung's width + 1, ..., all (``sort_ladder(32)`` is 0, 8, 16, 32)
WIDE_ROWS = 32
COUNTS = [0, 1, 8, 9, 16, 17, 32]
for _n in COUNTS:
    CASES[f"sampled_{_n}_of_{WIDE_ROWS}"] = _n


def _case_knobs(case, batch_seed=0):
    """(rows, (temp, top_k, top_p)) of a case.  A count case scatters its sampled rows
    (mixed knobs) among greedy rows that carry knobs and empty slots that carry
    ``pack_knobs``' zeros, by a permutation of the batch's seed."""
    if not isinstance(CASES[case], int):
        return ROWS, _knobs(*CASES[case])
    n, rows = CASES[case], WIDE_ROWS
    temp, top_k, top_p = (np.array(k) for k in _knobs(*(x * 2 for x in _MIXED), rows=rows))
    temp[temp <= 0.0] = 0.7
    where = np.random.RandomState(77 + batch_seed).permutation(rows)
    greedy, empty = where[n::2], where[n + 1 :: 2]
    temp[greedy], temp[empty], top_k[empty], top_p[empty] = 0.0, 0.0, 0, 0.0
    return rows, (jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p))


_sample_rows_jit = jax.jit(_sample_rows)
_verify_rows_jit = jax.jit(_verify_rows)


def _assert_same(got, want):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=1e-5, rtol=0)


def _assert_bit_equal(got, want):
    """Tokens equal, logprobs the same 32 bits."""
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(
        np.asarray(got[1]).view(np.uint32), np.asarray(want[1]).view(np.uint32)
    )


# -- (a) token for token against the oracle ------------------------------------


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16_ties"])
@pytest.mark.parametrize("case", list(CASES))
def test_decode_sampler_equals_full_sort_oracle(case, bf16):
    for batch_seed in range(3):
        rows, knobs = _case_knobs(case, batch_seed)
        if isinstance(CASES[case], int):
            assert int(np.sum(np.asarray(knobs[0]) > 0)) == CASES[case]
        logits = _logits(batch_seed, rows=rows, bf16=bf16)
        seeds, counters = _ids(batch_seed, rows=rows)
        got = _sample_rows_jit(logits, seeds, counters, *knobs)
        _assert_same(got, _oracle_sample_rows(logits, seeds, counters, *knobs))
        _assert_bit_equal(got, _full_width_rows(logits, seeds, counters, *knobs))


@pytest.mark.parametrize("case", ["greedy", "temperature_only", "chat_cell", "top_p_0.1"])
def test_one_row_the_prefill_sampler_shape(case):
    """The sampler at one row: what the prefill program runs on a prompt's last chunk
    (``model_runner._prefill_sample``).  Its ladder is (0, 1): none or all."""
    assert sort_ladder(1) == (0, 1)
    knobs = _knobs(*CASES[case], rows=1)
    for batch_seed in range(4):
        logits = _logits(batch_seed, rows=1, bf16=True)
        seeds, counters = _ids(batch_seed, rows=1)
        got = _sample_rows_jit(logits, seeds, counters, *knobs)
        _assert_same(got, _oracle_sample_rows(logits, seeds, counters, *knobs))
        _assert_bit_equal(got, _full_width_rows(logits, seeds, counters, *knobs))


@pytest.mark.parametrize("case", ["temperature_only", "chat_cell", "mixed_batch"])
def test_sample_tokens_one_key_a_batch_equals_oracle(case):
    """``sample_tokens_logprobs`` (models' ``generate``): rows draw from the splits of ONE key."""
    knobs = _knobs(*CASES[case])
    for batch_seed in range(3):
        logits, key = _logits(batch_seed, bf16=True), jax.random.PRNGKey(batch_seed)
        _assert_same(
            sample_tokens_logprobs(logits, key, *knobs), _oracle_sample(logits, key, *knobs)
        )


#: windows of 4 slots x 4 that sample, scattered: 0, 4, 8 (a rung's width), 12 (past
#: it) and 16 rows of ``sort_ladder(16)`` = (0, 8, 16)
_VERIFY_SAMPLED_SLOTS = {
    "sampled_slots_0": [], "sampled_slots_1": [2], "sampled_slots_2": [3, 0],
    "sampled_slots_3": [0, 1, 3], "sampled_slots_4": [0, 1, 2, 3],
}


@pytest.mark.parametrize(
    "case", ["greedy", "chat_cell", "mixed_batch", *_VERIFY_SAMPLED_SLOTS]
)
def test_verify_equals_oracle(case):
    slots, w = 4, 4
    if case in _VERIFY_SAMPLED_SLOTS:
        sampled = np.isin(np.arange(slots), _VERIFY_SAMPLED_SLOTS[case])
        temp, top_k, top_p = (k[1 : slots + 1] for k in _knobs(*_MIXED))
        temp = jnp.where(sampled, temp, 0.0)
        assert sort_rung(int(sampled.sum()) * w, slots * w) == (0, 1, 1, 2, 2)[sampled.sum()]
    else:
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
        # the window's rows, every one sorted: the same tokens, the same bits
        index = (counters[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]).reshape(-1)
        full = _full_width_rows(
            logits.reshape(slots * w, V), jnp.repeat(seeds, w), index,
            *(jnp.repeat(k, w) for k in (temp, top_k, top_p)),
        )
        _assert_bit_equal([x.reshape(-1) for x in got[1:]], full)


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


# -- (c) the program: one sort a rung, every one inside the switch, none wider ---


def _equations(jaxpr, inside_cond=False):
    """(equation, inside a cond's / switch's branch) of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_cond
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inside_cond or eqn.primitive.name == "cond")


@pytest.mark.parametrize("step", ["decode", "verify"])
def test_program_holds_one_sort_inside_the_conditional_and_no_scatter(step):
    """One ``switch`` on the count of sampled rows; one sort a sorting branch, over
    ``(w, v)`` with ``w`` a rung of the ladder; nothing gathers or scatters the
    vocabulary element by element (rows are gathered whole, the write-back is ``b``
    long); the greedy reductions stand outside, for every branch."""
    v = 512
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    ids = lambda n: (jax.ShapeDtypeStruct((n,), jnp.uint32), i32(n), f32(n), i32(n), f32(n))  # noqa: E731
    if step == "decode":
        b, fn, args = 32, _sample_rows, (f32(32, v), *ids(32))
    else:
        b, fn, args = 8 * 3, _verify_rows, (f32(8, 3, v), i32(8, 2), *ids(8))
    widths = sort_ladder(b)[1:]
    assert widths == {32: (8, 16, 32), 24: (8, 16, 24)}[b]

    eqns = list(_equations(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [e.primitive.name for e, _ in eqns]
    switch = [e for e, _ in eqns if e.primitive.name == "cond"]
    assert len(switch) == 1 and len(switch[0].params["branches"]) == 1 + len(widths)
    sorts = [(e, inside) for e, inside in eqns if e.primitive.name == "sort"]
    assert all(inside for _, inside in sorts)
    assert sorted(e.invars[0].aval.shape for e, _ in sorts) == [(w, v) for w in widths]
    # no operand of a scatter is vocabulary-wide, and a gather over one takes whole rows
    for e, _ in eqns:
        shapes = [x.aval.shape for x in e.invars]
        if e.primitive.name.startswith("scatter"):
            assert all(v not in shape for shape in shapes), shapes
        if e.primitive.name == "gather" and v in shapes[0]:
            assert shapes[0] == (b, v) and tuple(e.params["slice_sizes"]) == (1, v)
    assert "dynamic_slice" not in names
    # the greedy reductions stand outside the switch: they serve every branch
    assert ("argmax", False) in [(e.primitive.name, inside) for e, inside in eqns]

    text = jax.jit(fn).lower(*args).as_text()
    assert len(re.findall(r"stablehlo\.(case|if)\b", text)) == 1
    assert len(re.findall(r"stablehlo\.sort", text)) == len(widths)
    for w in widths:  # each sort's three results, (w, v) and no wider
        f, i = f"tensor<{w}x{v}xf32>", f"tensor<{w}x{v}xi32>"
        assert text.count(f"-> ({f}, {i}, {f})") == 1, w
    assert not re.search(rf"stablehlo\.scatter.*x{v}x", text)


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


def _tiny_engine(max_slots, num_blocks):
    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gptj import GPTJConfig, gptj_init

    cfg = GPTJConfig(
        vocab_size=128, seq_len=64, d_model=32, n_layers=2, n_heads=2, rotary_dim=8,
        dtype="float32", remat=False, attn_impl="xla", fused_loss=False,
    )
    return LLMEngine(
        cfg, gptj_init(jax.random.PRNGKey(0), cfg),
        EngineConfig(
            max_slots=max_slots, num_blocks=num_blocks, block_size=4, max_blocks_per_seq=10,
            prefill_chunk=8,
        ),
    )


def _sampler_stats(greedy_steps=0, sorted_steps=0, sorted_rows=0, sort_width_rows=0):
    return dict(
        greedy_steps=greedy_steps, sorted_steps=sorted_steps, sorted_rows=sorted_rows,
        sort_width_rows=sort_width_rows,
    )


def test_engine_counts_greedy_and_sorted_steps():
    from ray_tpu.llm import SamplingParams

    eng = _tiny_engine(max_slots=2, num_blocks=24)
    # two slots: the ladder's one sorting width is the batch itself
    width = sort_ladder(2)[sort_rung(1, 2)]
    assert sort_ladder(2) == (0, 2) and width == 2
    assert eng.stats()["sampler"] == _sampler_stats()
    prompt = [5, 9, 7, 5, 9, 7, 5, 9]
    # the first token comes from the prompt's last chunk: 5 tokens are 4 decodes
    eng.generate(prompt, SamplingParams(max_tokens=5))
    assert eng.stats()["sampler"] == _sampler_stats(greedy_steps=4)
    eng.generate(prompt, SamplingParams(max_tokens=4, temperature=0.8, top_k=5, seed=3))
    assert eng.stats()["sampler"] == _sampler_stats(4, 3, sorted_rows=3, sort_width_rows=3 * width)
    # one sampled row makes the batch a sorted one, for as long as it lives: a batch of
    # one sampled and one greedy row counts ONE sorted row at the rung's width
    long_greedy = eng.submit(prompt, SamplingParams(max_tokens=9))
    short_sampled = eng.submit(prompt, SamplingParams(max_tokens=3, temperature=1.0, seed=1))
    while not (long_greedy.finished and short_sampled.finished):
        eng.step()
    s = eng.stats()["sampler"]
    assert s["sorted_steps"] == 3 + 2 and s["greedy_steps"] > 4
    assert s["sorted_rows"] == 3 + 2 and s["sort_width_rows"] == (3 + 2) * width
    assert eng.stats()["retraces"] == 0


def test_ladder_and_rung_are_one_rule_on_host_and_device():
    assert sort_ladder(64) == (0, 8, 16, 32, 64) and sort_ladder(32) == (0, 8, 16, 32)
    assert sort_ladder(12) == (0, 8, 12) and sort_ladder(8) == (0, 8) and sort_ladder(3) == (0, 3)
    for b in (1, 2, 8, 12, 32, 64):
        ladder = sort_ladder(b)
        on_device = jax.jit(lambda n, b=b: sort_rung(n, b))
        for n in range(b + 1):
            width = ladder[sort_rung(n, b)]
            # the narrowest width that holds n rows; none for none
            assert width >= n and (width == 0) == (n == 0)
            assert all(w < n for w in ladder[: sort_rung(n, b)])
            assert int(on_device(jnp.int32(n))) == sort_rung(n, b)


def test_a_seeded_request_draws_the_same_alone_and_in_any_company():
    """The failover contract of ``models/sampling.py``'s module doc at the ladder: a
    request's tokens under a seed do not depend on how many rows sample beside it, so
    not on the width it was sorted at.  12 slots: widths (0, 8, 12)."""
    from ray_tpu.llm import SamplingParams

    eng = _tiny_engine(max_slots=12, num_blocks=12 * 10 + 1)
    assert sort_ladder(12) == (0, 8, 12)
    prompt = [5, 9, 7, 5, 9, 7, 5, 9]
    mine = SamplingParams(max_tokens=10, temperature=0.9, top_k=20, top_p=0.95, seed=1234)

    def run(company):
        before = eng.stats()["sampler"]
        reqs = [eng.submit(prompt, mine)] + [eng.submit([3 + i, 8, 2], p) for i, p in enumerate(company)]
        while not all(r.finished for r in reqs):
            eng.step()
        after = eng.stats()["sampler"]
        return list(reqs[0].out), {k: after[k] - before[k] for k in after}

    alone, d_alone = run([])
    beside_greedy, d_greedy = run([SamplingParams(max_tokens=12)] * 3)
    crowd = [SamplingParams(max_tokens=12, temperature=1.0, top_p=0.9, seed=50 + i) for i in range(9)]
    beside_sampled, d_crowd = run(crowd + [SamplingParams(max_tokens=12)])
    assert alone == beside_greedy == beside_sampled and len(alone) == 10
    # alone and beside greedy rows the first rung drew it; in the crowd 10 rows sampled
    assert d_alone["sort_width_rows"] == 8 * d_alone["sorted_steps"] > 0
    assert d_greedy["sorted_rows"] == d_greedy["sorted_steps"] == d_alone["sorted_steps"]
    assert d_greedy["sort_width_rows"] == 8 * d_greedy["sorted_steps"] and d_greedy["greedy_steps"] > 0
    assert d_crowd["sort_width_rows"] > 8 * d_crowd["sorted_steps"]
    assert d_crowd["sorted_rows"] > 8 * 5
    assert eng.stats()["retraces"] == 0
