"""Phi-4-mini-flash (state-space, window, full, memory-gate and cross layers
in ONE model) through the engine against its plain reference.

The reference (``benchmark/reference/phi4flash.py``) is the equations over
the whole sequence in float32: a loop for the scan, dense masked softmaxes,
no cache.  The engine serves chunks, rings written at ``position mod W``,
one paged K/V layer read by every cross layer, and carries scan and
convolution state from step to step.  Every comparison holds one to the
other on LOGITS, at a small size on the CPU in float32: 8 layers, every kind
in its ratio with the memory layer and the full-attention layer in the
middle, W = 16, so 40 decodes wrap every ring at least twice.

``TOL``: float32 round-off of two summation orders reads about 1e-6 on
logits of size 1; each named fault of the program reads 1e-2 and more
(``test_one_broken_thing_fails``), a bfloat16 scan state 1e-3 and more.
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

from benchmark.reference import phi4flash as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import HybridConfig, HybridPool  # noqa: E402
from ray_tpu.llm.model_runner import host_batch, pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.phi4flash import (  # noqa: E402
    Phi4FlashBody,
    Phi4FlashConfig,
    phi4flash_init,
)
from ray_tpu.ops import diff_attention as da  # noqa: E402
from ray_tpu.ops import selective_scan as ss  # noqa: E402

TOL = 1e-4
TINY = Phi4FlashConfig(vocab_size=192, d_model=64, n_layers=8, n_heads=8, n_kv_heads=4,
                       d_ff=96, sliding_window=16, d_inner=128, d_state=8, dt_rank=4,
                       init_range=0.1, dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    return phi4flash_init(jax.random.PRNGKey(0), TINY)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TINY.vocab_size, n)]


def _reference(tokens, rows):
    return np.asarray(reference.logits_at(
        _params(), tokens, rows, TINY.n_heads, TINY.n_kv_heads, TINY.sliding_window,
        TINY.layer_norm_eps, TINY.subln_eps))


@functools.lru_cache(maxsize=None)
def _runner(**over):
    return HybridModelRunner(dataclasses.replace(TINY, **over), _params(), block_size=BLOCK)


def _pool(runner, slots=SLOTS, fill=0.0):
    body = runner.body
    pool = HybridPool(HybridConfig(slots * TABLE + 1, BLOCK, TABLE, slots),
                      body.kv_layout(), body.state_leaves(BLOCK))
    if fill:  # a pool that starts as noise: nothing may be read before it is written
        pool.arrays = tuple(jnp.full(a.shape, fill, a.dtype) for a in pool.arrays)
    return pool


def _teacher_forced(runner, n_prompt=21, n_out=44, fill=0.0):
    """Prefill ``n_prompt`` tokens in chunks, then decode the sequence's own
    next tokens one step at a time in batch row 1, beside two dead rows.
    Returns (reference logits, engine logits) at the chunks' last tokens and
    at every decode position."""
    seq = _prompt(2, n_prompt + n_out)
    pool = _pool(runner, fill=fill)
    pool.allocate("other", 4)  # so the sequence does not sit in the first slot
    pool.allocate("seq", len(seq))
    table, rows, got = pool.table_row("seq"), [], []
    for pos in range(0, n_prompt, CHUNK):
        piece = seq[pos:min(pos + CHUNK, n_prompt)]
        buf = np.zeros(CHUNK, np.int32)
        buf[:len(piece)] = piece
        *arrays, logits, _, _ = runner.prefill_chunk(
            *pool.arrays, buf, pos, len(piece), table, GREEDY)
        pool.arrays = arrays
        rows.append(pos + len(piece) - 1)
        got.append(np.asarray(logits))
    step = jax.jit(runner._decode_logits)
    tables = np.stack([pool.table_row(None), table, pool.table_row(None)])
    for i in range(n_prompt, n_prompt + n_out):
        tokens = np.array([0, seq[i], 0], np.int32)
        positions = np.array([0, i, 0], np.int32)
        pool.arrays, logits = step(runner.params, pool.arrays, tokens, positions, tables)
        rows.append(i)
        got.append(np.asarray(logits[1]))
    return _reference(seq, rows), np.stack(got)


# -- the engine's steps against the reference ---------------------------------------


def test_chunks_then_decodes_through_wrapping_rings_match_the_reference():
    # three chunks (the last ragged), then 44 decodes: W = 16 wraps four times
    want, got = _teacher_forced(_runner(), fill=3.0)
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5  # the logits are not all alike


# One departure from the equations a case, planted HERE by overriding one
# small method of the body: the served programs hold no such switch.


class _NoWindowMask(Phi4FlashBody):
    def _ring_seen(self, positions, held):
        return jnp.broadcast_to(held[None, :] >= 0, (positions.shape[0], held.shape[0]))


class _RingNotWrapped(Phi4FlashBody):
    def _ring_entry(self, position):
        return jnp.minimum(position, self.cfg.sliding_window - 1)


class _CrossBehindKV(Phi4FlashBody):
    """The cross layers read the shared K/V as it stood before this token's
    write (a cross layer has no key or value of its own to read instead)."""

    def _upper(self, params, x, memory, k_pool, v_pool, btab, positions):
        return super()._upper(params, x, memory, k_pool, v_pool, btab, positions - 1)


class _WrongMemory(Phi4FlashBody):
    """The memory is the scan output of the state-space layer two below."""

    def _memory_layer(self, step, params, x, conv, scan, slot):
        out = super()._memory_layer(step, params, x, conv, scan, slot)
        below = jax.tree_util.tree_map(lambda a: a[-1], params["seg1"]["ssm"])
        flat = lambda p: p.reshape((-1,) + p.shape[2:])  # noqa: E731
        at = (self.n_ssm - 2) * conv.shape[1] + slot
        return (out[0], step(x, below, flat(conv), flat(scan), at)[1]) + out[2:]


class _LambdaFixed(Phi4FlashBody):
    def _lambda(self, vectors, lam0):
        return lam0


@pytest.mark.parametrize(
    "broken", [_NoWindowMask, _RingNotWrapped, _CrossBehindKV, _WrongMemory, _LambdaFixed])
def test_one_broken_thing_fails(broken):
    class Config(Phi4FlashConfig):
        def serving_body(self):
            return broken(self)

    runner = HybridModelRunner(Config(**dataclasses.asdict(TINY)), _params(), block_size=BLOCK)
    want, got = _teacher_forced(runner)
    assert np.abs(want - got).max() > 100 * TOL


def test_a_bfloat16_scan_state_fails_the_tolerance():
    want, got = _teacher_forced(_runner(state_dtype="bfloat16"))
    assert np.abs(want - got).max() > 10 * TOL


def test_the_pallas_path_equals_the_xla_path():
    """The paged kernel (interpreted here) behind the differential pair
    form, at a head size that tiles."""
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    rows, heads, kv, e, block, tmax = 3, 8, 4, 64, 8, 4
    q, k_pool, v_pool = f(rows, heads, e), f(13, kv // 2, block, 2 * e), f(13, kv // 2, block, 2 * e)
    tables = jnp.asarray(rng.permutation(12)[:rows * tmax].reshape(rows, tmax) + 1, jnp.int32)
    positions = jnp.asarray([5, -1, 30], jnp.int32)  # the middle row attends nothing
    out = {impl: da.diff_paged_attention(q, k_pool, v_pool, tables, positions, kv, impl=impl)
           for impl in ("xla", "pallas")}
    for a, b in zip(out["xla"], out["pallas"]):
        np.testing.assert_allclose(a[::2], b[::2], rtol=2e-5, atol=2e-5)
    # and the dense form a chunk uses: the same numbers from gathered keys
    keys = k_pool[tables[2]].transpose(0, 2, 1, 3).reshape(-1, kv // 2, 2 * e)
    vals = v_pool[tables[2]].transpose(0, 2, 1, 3).reshape(-1, kv // 2, 2 * e)
    dense = da.diff_dense_attention(q[2:3], keys, vals, (jnp.arange(tmax * block) <= 30)[None])
    for a, b in zip(out["xla"], dense):
        np.testing.assert_allclose(a[2], b[0], rtol=2e-5, atol=2e-5)


def test_the_scan_in_chunks_equals_the_scan_token_by_token():
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    T, N, D = 37, 8, 32
    u, b, c, skip = f(T, D), f(T, N), f(T, N), f(D)
    delta, a = jnp.abs(f(T, D)) * 0.1, -jnp.abs(f(N, D))
    s, want = jnp.zeros((1, N, D)), []
    for t in range(T):
        s, m = ss.scan_decode(s, u[t:t + 1], delta[t:t + 1], a, b[t:t + 1], c[t:t + 1], skip)
        want.append(m[0])
    valid = jnp.arange(48) < T  # a padded tail leaves the state as it was
    pad = lambda x: jnp.pad(x, ((0, 48 - T), (0, 0)), constant_values=7.0)  # noqa: E731
    got, last = ss.scan_chunk(jnp.zeros((N, D)), pad(u), pad(delta), a, pad(b), pad(c),
                              skip, valid)
    np.testing.assert_allclose(got[:T], jnp.stack(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(last, s[0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_pools_and_states_are_updated_in_place(step):
    """No pool-sized temporary in either step (as ``test_llm_brumby_parity``
    and ``test_llm_pool_inplace`` hold theirs): at pools made large against
    the model, the compiled program's temporaries stay under a quarter of
    them and every pool is aliased to its output.  The decode has 4 rows on
    pools of 64 slots: the XLA attention form gathers its rows' whole tables,
    which is then a sixteenth of the pools and not all of them."""
    cfg = dataclasses.replace(TINY, sliding_window=64)
    runner = HybridModelRunner(cfg, phi4flash_init(jax.random.PRNGKey(0), cfg), block_size=BLOCK)
    rows, i32 = 4, np.int32
    pool = _pool(runner, slots=64)
    pools = sum(a.nbytes for a in pool.arrays)
    if step == "decode":
        z = np.zeros(rows)
        ops = host_batch(z.astype(i32), z.astype(i32), np.zeros((rows, 1 + TABLE), i32),
                         z, z, np.ones(rows), z, z)
        lowered = runner._decode.lower(runner.params, *pool.arrays, *ops)
    else:
        lowered = runner._prefill.lower(
            runner.params, *pool.arrays, np.zeros(CHUNK, i32), i32(0), i32(CHUNK),
            np.zeros(1 + TABLE, i32), GREEDY, chunk=CHUNK)
    mem = lowered.compile().memory_analysis()
    # prefill: this CPU backend lays the first segment's carries (states and
    # rings, 3.2 of the 5.3 MB) out anew ONCE for the products that read one
    # slot of them, as it does Brumby's; the chip's compiler does not (0.055
    # GB beside 1.18 GB of pools: the configuration's ``memory``).  Pools as
    # the scan's ``xs``/``ys`` would be two copies and more
    bound = 0.25 if step == "decode" else 0.75
    assert mem.temp_size_in_bytes < bound * pools, (mem.temp_size_in_bytes, pools)
    assert mem.alias_size_in_bytes >= pools


# -- the two-ledger pool ----------------------------------------------------------------


def test_admission_needs_a_slot_and_blocks():
    body = _runner().body  # 2 slots, 40 usable blocks of 4 tokens
    pool = HybridPool(HybridConfig(41, BLOCK, TABLE, 2), body.kv_layout(),
                      body.state_leaves(BLOCK))
    assert pool.can_allocate(100)
    assert len(pool.allocate("a", 100)) == 25 and pool.states.blocks_of("a")[0] in (1, 2)
    row = pool.table_row("a")
    assert row.shape == (1 + TABLE,) and row[0] == pool.states.blocks_of("a")[0] and row[26] == 0
    assert not pool.table_row(None).any()  # the trash slot, the trash block
    # blocks short (15 free, 16 asked), a slot free
    assert not pool.can_allocate(64)
    with pytest.raises(MemoryError):
        pool.allocate("b", 64)
    assert pool.states.num_free_blocks == 1  # the failed allocate gave its slot back
    pool.allocate("b", 8)
    # slots short, blocks free
    assert not pool.can_allocate(4)
    with pytest.raises(MemoryError):
        pool.allocate("c", 4)
    assert pool.grow_to("a", 128) and not pool.grow_to("a", 129)  # the table's width
    assert pool.grow_to("b", 32) and not pool.grow_to("b", 33)    # the pool's last block
    counts = pool.ledger_counts()
    assert counts["seq_owned"] == 40 and counts["slots_owned"] == 2 and counts["slots_free"] == 0
    audit = pool.audit()
    assert audit["ok"] and sorted(audit["owners"]) == ["a", "b"] and audit["slots"]["owned"] == 2
    pool.states.free("b")  # a sequence with blocks and no slot
    assert not pool.audit()["ok"] and pool.audit()["unpaired"] == ["b"]
    pool.free("b")
    assert pool.free("a") == 32 and pool.audit()["ok"] and pool.audit()["free"] == 40
    assert pool.states.audit()["free"] == 2


def _drive(eng, reqs):
    while not all(r.finished for r in reqs):
        eng.step()
    return [list(r.out) for r in reqs]


def test_preempted_for_blocks_and_resumed_token_for_token():
    """Few blocks: sequences growing past them are preempted (recompute:
    the next first chunk overwrites a slot) and must give the tokens of an
    engine that never preempts."""
    prompts = [_prompt(30 + i, 12 + 5 * i) for i in range(4)]
    outs = []
    for blocks in (SLOTS * TABLE + 1, 26):
        eng = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, num_blocks=blocks)))
        reqs = [eng.submit(p, SamplingParams(max_tokens=40)) for p in prompts]
        outs.append(_drive(eng, reqs))
        stats = eng.stats()
        assert (stats["preemptions"] > 0) == (blocks == 26)
        assert eng.pool.audit()["ok"] and eng.pool.audit()["owned"] == 0
    assert outs[0] == outs[1]
    for prompt, out in zip(prompts, outs[1]):
        seq = prompt + out
        logits = _reference(seq, list(range(len(prompt) - 1, len(seq) - 1)))
        assert (logits.max(-1) - logits[np.arange(len(out)), out]).max() < TOL


@pytest.mark.time_limit(300)
def test_the_audit_holds_after_a_churn_of_200_requests():
    eng = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, num_blocks=40)))
    rng = np.random.default_rng(5)
    reqs = [eng.submit(_prompt(100 + i, int(rng.integers(3, 30))),
                       SamplingParams(max_tokens=int(rng.integers(2, 12)),
                                      temperature=float(i % 2) * 0.8, seed=i))
            for i in range(200)]
    for i, r in enumerate(reqs):
        if i % 17 == 0:
            eng.cancel(r.id)
    _drive(eng, reqs)
    audit, s = eng.pool.audit(), eng.stats()
    assert audit["ok"] and audit["owned"] == 0 and audit["free"] == 39
    assert audit["slots"]["free"] == SLOTS and not audit["unpaired"]
    assert s["state_pool"]["slots"] == SLOTS and s["state_pool"]["live"] == 0
    # the decodes' occupancy stands under both pools, the same three counts
    assert s["kv_pool"] == {"blocks": 39, "live": 0, "block_tokens": BLOCK,
                            "bytes": eng.pool.kv.device_bytes,
                            **{k: s["state_pool"][k] for k in ("decodes", "decode_rows", "decode_tokens")}}
    assert set(s["state_pool"]["kinds"]) == {"conv", "scan", "ring_k", "ring_v"}
    assert s["state_pool"]["decode_tokens"] > s["state_pool"]["decode_rows"] > 0
    assert s["hbm"]["pool_bytes"] == eng.pool.device_bytes


@pytest.mark.parametrize("knob,why", [
    (dict(prefix_cache=True), "state snapshots"),
    (dict(prefix_cache=False, spec_k=2), "roll it back"),
    (dict(prefix_cache=False, tp=2), "no sharded form"),
])
def test_the_engine_refuses_what_a_state_cannot_do(knob, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **knob)))


def test_the_family_is_found_by_name_at_the_published_widths():
    from benchmark import harness as H
    from ray_tpu.serve.llm import _FAMILIES, _build_model, build_llm_app

    config = H.load_config(H.manifest(), "phi4-mini-flash-1chip")
    assert config["reduced"] == {}
    cfg = H.family_piece(config, "model_config")(H.sizes(config, False))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size,
            cfg.n_layers, cfg.sliding_window, cfg.d_inner, cfg.d_state, cfg.dt_rank) == (
                2560, 40, 20, 64, 10240, 200064, 32, 512, 5120, 16, 160)
    kinds = cfg.layer_kinds()
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:18] == ["ssm", "full"]
    body = cfg.serving_body()
    assert body.kv_layout() == {"n_layers": 1, "n_heads": 10, "head_dim": 128,
                                "dtype": "bfloat16"}
    leaves = body.state_leaves(16)
    assert leaves["scan"] == (9, (16, 5120), "float32")
    assert leaves["ring_k"] == (8, (32, 10, 16, 128), "bfloat16")
    shapes = jax.eval_shape(lambda: phi4flash_init(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 3.84e9 < n < 3.86e9  # 3.85B, the card's 3.8B
    model = dataclasses.asdict(cfg)
    need = H.family_piece(config, "shared_kv_decode_bytes")(32 * 850, model)
    assert need == 8 * 32 * 850 * 5120
    got, _ = _build_model("phi4flash", TINY, _params(), seed=0)
    assert got is TINY and build_llm_app(model="phi4flash", model_cfg=TINY) is not None
    with pytest.raises(TypeError):
        _build_model("phi4flash", object(), None, seed=0)
    with pytest.raises(ValueError, match=", ".join(repr(f) for f in _FAMILIES)):
        _build_model("nope", None, None, seed=0)
