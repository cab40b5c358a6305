"""Brumby (power retention) through the engine against its plain reference.

The reference (``benchmark/reference/brumby.py``) is the ATTENTION form in
float32: no state, no feature map.  The engine serves the recurrent form:
chunked prefill carries a state from chunk to chunk, decode updates it in
place.  So every comparison here holds one form to the other, on LOGITS
(with random weights the largest logit changes on rounding), at a small
size on the CPU, float32 activations.

``TOL`` is the tolerance: float32 round-off of two different summation
orders over a few hundred tokens reads 2e-6 on logits of size 3; a state
held in bfloat16 reads 4e-3 to 1e-2 (``test_a_bfloat16_state_fails``), so
1e-4 stands a factor of 40 from each.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import brumby as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import StateConfig, StatePool  # noqa: E402
from ray_tpu.llm.model_runner import host_batch, pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import StateModelRunner  # noqa: E402
from ray_tpu.models.brumby import BrumbyConfig, brumby_init  # noqa: E402
from ray_tpu.ops import power_retention as pr  # noqa: E402

TOL = 1e-4
TINY = BrumbyConfig(vocab_size=192, seq_len=512, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, head_dim=16, d_ff=96, dtype="float32",
                    retention_impl="xla")
SLOTS, CHUNK = 4, 16
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    return brumby_init(jax.random.PRNGKey(0), TINY)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TINY.vocab_size, n)]


def _reference(tokens, rows, cfg=TINY):
    return np.asarray(reference.logits_at(
        _params(), tokens, rows, cfg.n_heads, cfg.n_kv_heads, cfg.rms_eps,
        cfg.rope_theta, cfg.gate_shift, cfg.retention_eps))


@functools.lru_cache(maxsize=None)
def _runner(**over):
    return StateModelRunner(dataclasses.replace(TINY, **over), _params())


def _new_state(runner, fill=0.0):
    shape = (TINY.n_layers, SLOTS) + runner.body.state_shape
    return jnp.full(shape, fill, jnp.dtype(runner.body.state_dtype))


def _prefill(runner, state, tokens, slot, chunk=CHUNK):
    """Chunked prefill of ``tokens`` into ``slot``. Returns (state, [(row,
    logits)]): the logits each chunk leaves for its last valid token."""
    out, table = [], np.array([slot], np.int32)
    for pos in range(0, len(tokens), chunk):
        piece = tokens[pos:pos + chunk]
        buf = np.zeros(chunk, np.int32)
        buf[:len(piece)] = piece
        state, logits, _, _ = runner.prefill_chunk(state, buf, pos, len(piece), table, GREEDY)
        out.append((pos + len(piece) - 1, np.asarray(logits)))
    return state, out


# -- the engine's steps against the reference ---------------------------------------


@pytest.mark.parametrize("chunk", [CHUNK, 32])
def test_chunked_prefill_logits_match_the_attention_form(chunk):
    prompt = _prompt(1, 150)  # nine chunk boundaries at 16
    runner = _runner()
    _, got = _prefill(runner, _new_state(runner), prompt, slot=2, chunk=chunk)
    want = _reference(prompt, [row for row, _ in got])
    assert np.abs(want - np.stack([lg for _, lg in got])).max() < TOL


def _teacher_forced(runner, n_prompt=40, n_out=12, noise=0.0):
    """Prefill a prompt into slot 1, then decode the sequence's own next
    tokens through the state one step at a time, in batch row 2 beside
    three dead rows: (reference logits, engine logits) at the decode
    positions.  The logits are the decode program's own, before its
    sampler (``StateModelRunner._decode_logits``)."""
    seq = _prompt(2, n_prompt + n_out)
    state, _ = _prefill(runner, _new_state(runner, noise), seq[:n_prompt], slot=1)
    step = jax.jit(runner._decode_logits)
    slots, alive = np.array([0, 0, 1, 1], np.int32), np.array([False, False, True, False])
    got = []
    for i in range(n_prompt, n_prompt + n_out):
        state, logits = step(runner.params, state, np.full(SLOTS, seq[i], np.int32),
                             np.full(SLOTS, i, np.int32), slots, alive)
        got.append(np.asarray(logits[2]))
    return _reference(seq, list(range(n_prompt, n_prompt + n_out))), np.stack(got)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_through_the_state_matches_the_attention_form(impl):
    # a pool that starts as noise: the first chunk must overwrite its slot
    want, got = _teacher_forced(_runner(retention_impl=impl), noise=3.0)
    assert np.abs(want - got).max() < TOL


@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_a_bfloat16_state_fails_the_tolerance(path):
    runner = _runner(state_dtype="bfloat16")
    if path == "prefill":
        prompt = _prompt(1, 150)
        _, got = _prefill(runner, _new_state(runner), prompt, slot=0)
        want, got = _reference(prompt, [r for r, _ in got]), np.stack([lg for _, lg in got])
    else:
        want, got = _teacher_forced(runner)
    assert np.abs(want - got).max() > 10 * TOL


def test_a_reused_slot_equals_a_fresh_one():
    """The overwrite rule: a slot's next owner starts from nothing, whatever
    the last owner left; and through the engine, tokens and state alike."""
    runner = _runner()
    a, b = _prompt(3, 70), _prompt(4, 45)
    used, _ = _prefill(runner, _new_state(runner), a, slot=3)
    used, got = _prefill(runner, used, b, slot=3)
    fresh, want = _prefill(runner, _new_state(runner), b, slot=3)
    assert all(np.array_equal(g, w) for (_, g), (_, w) in zip(got, want))
    assert np.array_equal(np.asarray(used[:, 3]), np.asarray(fresh[:, 3]))

    eng = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, max_slots=1)))
    eng.generate(a, SamplingParams(max_tokens=9))
    again = eng.generate(b, SamplingParams(max_tokens=9))
    other = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, max_slots=1)))
    assert again == other.generate(b, SamplingParams(max_tokens=9))
    assert eng.stats()["state_pool"]["overwrites"] == 2


# -- the op: both forms, both implementations ----------------------------------------


def _op_inputs(seed, rows, d=16, hq=4, h=2, pool=6, past=0):
    """A pool of states and a batch of rows.  ``past`` = 0: states of normal
    draws (a normaliser may fall near 0); otherwise each is what ``past``
    tokens left, so its normaliser is a sum of squares."""
    rng = np.random.default_rng(seed)
    vd, F = pr.state_dims(d)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    if past:
        state = jnp.einsum("nhsv,nhsf->nhvf", pr._v_ext(f(pool, h, past, d), vd),
                           pr.phi(f(pool, h, past, d)), precision="highest")
    else:
        state = f(pool, h, vd, F)
    log_g = jnp.asarray(-rng.uniform(0.001, 0.2, (rows, h)), jnp.float32)
    return state, f(rows, hq, d), f(rows, h, d), f(rows, h, d), log_g


#: the parity tests' head and the PUBLISHED one (a state of (136, 8320): 17 groups
#: of eight value rows, which no block of four divides, by 65 lane vregs, 5 query heads)
HEADS = {"tiny": dict(d=16, hq=4, h=2, pool=6, slots=[5, 0, 0, 2]),
         "published": dict(d=128, hq=5, h=1, pool=3, past=8, slots=[2, 0, 0, 1])}


def _head_inputs(head, seed=0):
    shape = dict(HEADS[head])
    slots = jnp.asarray(shape.pop("slots"), jnp.int32)  # a dead row names a live row's slot
    return _op_inputs(seed, 4, **shape), slots


@pytest.mark.parametrize("live", [
    [True, True, True, True], [False, True, False, True], [True, False, False, False],
    [False, False, False, False]])
@pytest.mark.parametrize("head", list(HEADS))
def test_the_pallas_kernel_equals_the_xla_form_and_spares_dead_rows(head, live):
    (state, q, k, v, log_g), slots = _head_inputs(head)
    live = jnp.asarray(live)
    out = {}
    for impl in ("xla", "pallas"):
        out[impl] = pr.retention_decode(
            jnp.array(state), q, k, v, log_g, slots, live, eps=1e-6, impl=impl)
    np.testing.assert_allclose(out["xla"][0], out["pallas"][0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out["xla"][1], out["pallas"][1], rtol=1e-5, atol=1e-5)
    touched = {int(s) for s, a in zip(slots, live) if a}
    for slot in range(state.shape[0]):
        same = np.array_equal(np.asarray(out["pallas"][0][slot]), np.asarray(state[slot]))
        assert same == (slot not in touched), slot
    assert not np.asarray(out["pallas"][1])[~np.asarray(live)].any()


@pytest.mark.parametrize("head", list(HEADS))
def test_the_read_out_never_reaches_the_stored_state(head):
    """The kernel reads ``y`` out of the state it has just updated, in an order
    of its own: two calls that differ in ``q`` alone read different ``y`` and
    store the SAME bits."""
    (state, q, k, v, log_g), slots = _head_inputs(head, seed=1)
    live = jnp.asarray([True, True, False, True])
    first, second = (pr.retention_decode(jnp.array(state), each, k, v, log_g, slots, live,
                                         eps=1e-6, impl="pallas")
                     for each in (q, 1.0 + q[::-1]))
    assert not np.array_equal(np.asarray(first[1]), np.asarray(second[1]))
    assert np.array_equal(np.asarray(first[0]), np.asarray(second[0]))


def test_the_recurrent_form_equals_the_attention_form_over_300_steps():
    T, d, hq, h = 300, 16, 4, 2
    _, q, k, v, log_g = _op_inputs(1, T, d, hq, h)
    vd, F = pr.state_dims(d)
    state = jnp.zeros((1, h, vd, F), jnp.float32)
    step = jax.jit(lambda s, q, k, v, g: pr.retention_decode(
        s, q[None], k[None], v[None], g[None], jnp.zeros(1, jnp.int32),
        jnp.ones(1, bool), eps=1e-6, impl="xla"))
    got = []
    for t in range(T):
        state, y = step(state, q[t], k[t], v[t], log_g[t])
        got.append(np.asarray(y[0]))
    q64, k64, v64 = (np.asarray(x, np.float64) for x in (q, k, v))
    a = np.cumsum(np.asarray(log_g, np.float64), axis=0)
    causal = np.tril(np.ones((T, T), bool))
    for j in range(hq):
        kv = j // (hq // h)
        w = np.exp(a[:, None, kv] - a[None, :, kv]) * (q64[:, j] @ k64[:, kv].T) ** 2 / d
        w = np.where(causal, w, 0.0)
        want = (w @ v64[:, kv]) / (w.sum(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.stack(got)[:, j], want, rtol=2e-4, atol=2e-5)
    # a chunk of the same tokens leaves the same state
    _, s1 = pr.retention_chunk(jnp.zeros((h, vd, F)), q, k, v, log_g,
                               jnp.ones(T, bool), eps=1e-6)
    np.testing.assert_allclose(s1, state[0], rtol=1e-4, atol=1e-5)


def test_phi_is_the_symmetric_square_and_its_padding_is_zero():
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(size=(3, 16)), jnp.float32) for _ in range(2))
    assert pr.state_dims(128) == (136, 8320)  # for (129, 8256)
    np.testing.assert_allclose(
        (pr.phi(q) * pr.phi(k)).sum(-1), (q * k).sum(-1) ** 2 / 16, rtol=1e-5)
    assert pr.phi(q).shape == (3, 256) and not np.asarray(pr.phi(q))[:, 136:].any()


# -- the engine around it -------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    """ONE engine at ``ENGINE``'s sizes for the cases that serve through it,
    its two programs compiled once a module.  Its counters only grow: a case
    reads what ITS requests added."""
    return LLMEngine(TINY, _params(), EngineConfig(**ENGINE))


def _drive(eng, reqs, serial=False):
    while not all(r.finished for r in reqs):
        eng.step()
        if serial:
            with eng._lock:
                eng._drain("serial")
    return [list(r.out) for r in reqs]


@pytest.mark.parametrize("sampled", [False, True])
def test_launch_ahead_and_the_serial_path_give_the_same_tokens(engine, sampled):
    knobs = dict(temperature=0.8, top_k=12, top_p=0.9) if sampled else {}
    outs, eng = [], engine
    for serial in (False, True):
        before = eng.stats()["pipeline"]["ahead_steps"]
        reqs = [eng.submit(_prompt(10 + i, 20 + 9 * i),
                           SamplingParams(max_tokens=14 + i, seed=i, **knobs))
                for i in range(6)]  # more than the slots: two wait their turn
        outs.append(_drive(eng, reqs, serial))
        assert (eng.stats()["pipeline"]["ahead_steps"] > before) != serial
        audit = eng.pool.audit()
        assert audit["ok"] and audit["owned"] == 0 and audit["free"] == SLOTS
    assert outs[0] == outs[1]


def test_served_tokens_are_the_references_choice(engine):
    eng = engine
    reqs = [eng.submit(_prompt(20 + i, 30 + 11 * i), SamplingParams(max_tokens=8))
            for i in range(3)]
    for req, out in zip(reqs, _drive(eng, reqs)):
        seq = req.prompt + out
        rows = list(range(len(req.prompt) - 1, len(seq) - 1))
        logits = _reference(seq, rows)
        chosen = logits[np.arange(len(out)), out]
        assert (logits.max(-1) - chosen).max() < TOL
    s = eng.stats()
    assert s["state_pool"]["slots"] == SLOTS and s["state_pool"]["live"] == 0
    assert s["state_pool"]["bytes"] == eng.pool.device_bytes == eng.pool.state.nbytes
    assert s["state_pool"]["decode_rows"] >= s["state_pool"]["decodes"] > 0
    assert s["preemptions"] == 0 and "prefix_cache" not in s


def test_the_state_pools_ledger_and_its_audit():
    pool = StatePool(StateConfig(3, 64), n_layers=2, state_shape=(2, 8, 128))
    assert pool.state.shape == (2, 3, 2, 8, 128) and pool.block_bytes * 3 == pool.device_bytes
    assert pool.can_allocate(64) and not pool.can_allocate(65)
    got = [pool.allocate(f"r{i}", 10)[0] for i in range(3)]
    assert sorted(got) == [0, 1, 2] and not pool.can_allocate(1)
    assert pool.grow_to("r0", 64) and not pool.grow_to("r0", 65)
    assert int(pool.table_row("r1")[0]) == got[1] and int(pool.table_row(None)[0]) == 0
    assert pool.ledger_counts() == {"free": 0, "seq_owned": 3, "cache_only": 0}
    with pytest.raises(MemoryError):
        pool.allocate("r3", 1)
    with pytest.raises(ValueError):
        pool.allocate("r0", 1)
    assert pool.free("r1") == 1 and pool.free("r1") == 0 and pool.utilization() == 2 / 3
    audit = pool.audit()
    assert audit["ok"] and audit["free"] == 1 and sorted(audit["owners"]) == ["r0", "r2"]
    pool._free.append(got[0])  # a slot both free and owned
    assert not pool.audit()["ok"] and pool.audit()["duplicates"]
    pool._free[:] = []  # a slot nobody holds
    assert not pool.audit()["ok"] and pool.audit()["missing"] == 1


@pytest.mark.parametrize("knob,why", [
    (dict(prefix_cache=True), "no keys or values"),
    (dict(prefix_cache=False, spec_k=2), "roll it back"),
    (dict(prefix_cache=False, tp=2), "no sharded form"),
])
def test_the_engine_refuses_what_is_built_on_kv_blocks(knob, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(TINY, _params(), EngineConfig(max_slots=2, **knob))


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_the_state_pool_is_updated_in_place(step):
    """No pool-sized temporary: the compiled step's temporaries stay under
    half of the pool at a pool made large against the model (as
    ``test_llm_pool_inplace.py`` holds the K/V steps).  With the pool as the
    scan's ``xs``/``ys`` the temporary is the pool's size and more."""
    cfg = dataclasses.replace(TINY, n_layers=12, head_dim=32, n_heads=2, n_kv_heads=1)
    runner = StateModelRunner(cfg, brumby_init(jax.random.PRNGKey(0), cfg))
    slots = 8
    state = jnp.zeros((cfg.n_layers, slots) + runner.body.state_shape, jnp.float32)
    i32 = np.int32
    if step == "decode":
        z = np.zeros(slots)
        ops = host_batch(z.astype(i32), z.astype(i32), np.zeros((slots, 1), i32),
                         z, z, np.ones(slots), z, z)
        lowered = runner._decode.lower(runner.params, state, *ops)
    else:
        lowered = runner._prefill.lower(
            runner.params, state, np.zeros(CHUNK, i32), i32(0), i32(CHUNK),
            np.zeros(1, i32), GREEDY, chunk=CHUNK)
    mem = lowered.compile().memory_analysis()
    # decode: the CPU's XLA form gathers, updates and scatters ONE layer's
    # rows, three twelfths of the pool (the chip's kernel: 2 MB beside 4.6
    # GB).  prefill: this CPU backend lays the whole carry out anew for the
    # matrix product that reads one slot of it, ONE pool-sized copy (the
    # chip's compiler does not: 0.19 GB beside 4.6 GB, the configuration's
    # ``memory``); the pool as ``xs``/``ys`` would be two and more
    bound = 0.5 if step == "decode" else 1.25
    assert mem.temp_size_in_bytes < bound * state.nbytes, (
        mem.temp_size_in_bytes, state.nbytes)
    assert mem.alias_size_in_bytes >= state.nbytes


def test_the_family_is_found_by_name_at_the_published_widths():
    from benchmark import harness as H
    from ray_tpu.serve.llm import _build_model, build_llm_app

    config = H.load_config(H.manifest(), "brumby-14b-l8-1chip")
    cfg = H.family_piece(config, "model_config")(H.sizes(config, False))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size, cfg.seq_len, cfg.n_layers) == (
                5120, 40, 8, 128, 17408, 151936, 32768, 8)
    assert cfg.state_dtype == "float32" and cfg.serving_body().state_shape == (8, 136, 8320)
    need = H.family_piece(config, "retention_decode_state_bytes")(16, dataclasses.asdict(cfg))
    assert need == 16 * 8 * 8 * 8256 * 129 * 4 * 2
    got, _ = _build_model("brumby", TINY, _params(), seed=0)
    assert got is TINY and build_llm_app(model="brumby", model_cfg=TINY) is not None
    with pytest.raises(TypeError):
        _build_model("brumby", object(), None, seed=0)
