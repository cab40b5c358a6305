"""AFMoE (window and full attention layers in ONE model, a gated attention,
four norms a layer, a share of an expert layer) through the engine against its
plain reference, past the window and past several released blocks.

The reference (``benchmark/reference/afmoe.py``) is the equations over the
whole sequence in float32: a dense masked softmax with the window as a MASK
over every key, a loop over the held experts, no cache.  The engine serves
chunks over TWO block tables, hands the window layers' blocks behind the
window back (``llm.cache.LayerTypedPool``) and decodes through what is left.
Every comparison holds one to the other on LOGITS, at a small size on the CPU
in float32: five layers ``sliding, sliding, full, sliding, sliding`` (the
published layers 5-9: one dense, four with experts), 4 query heads on 2
key-value heads, a window of 12 in blocks of 4 and chunks of 8, experts 4-7 of
16 held, 2 a token.

``TOL``: float32 round-off of two summation orders reads about 1e-5 on logits
of size 1; each named fault of the program reads 1e-2 and more
(``test_one_broken_thing_fails``).
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

from benchmark.families import afmoe as family  # noqa: E402
from benchmark.reference import afmoe as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import LayerTypedConfig, LayerTypedPool  # noqa: E402
from ray_tpu.llm.model_runner import pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.afmoe import LAYERS_5_TO_9, AfmoeBody, AfmoeConfig, afmoe_init  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

TOL = 1e-3
W = 12
TINY = AfmoeConfig(
    vocab_size=160, seq_len=4096, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, window=W,
    d_ff=96, d_expert=24, n_routed_experts=16, experts_held=4, expert_offset=4,
    expert_parallel=4, experts_per_tok=2, init_range=0.5, score_spread=4.0,
    expert_out_gain=1.0, dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)
CAP = W // BLOCK + CHUNK // BLOCK + 1


@functools.lru_cache(maxsize=None)
def _params():
    return afmoe_init(jax.random.PRNGKey(0), TINY)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TINY.vocab_size, n)]


def _reference(tokens, rows):
    return np.asarray(family.reference_logits(_params(), tokens, rows, TINY))


@functools.lru_cache(maxsize=None)
def _runner(body=None, **over):
    cfg = dataclasses.replace(TINY, **over)
    if body is not None:
        cfg = _With(cfg, body)
    return HybridModelRunner(cfg, _params(), block_size=BLOCK)


class _With:
    """A configuration that serves through another body (a planted fault)."""

    def __init__(self, cfg, body):
        self._cfg, self._body = cfg, body

    def __getattr__(self, name):
        return getattr(self._cfg, name)

    def serving_body(self):
        return self._body(self._cfg)


def _pool(runner):
    cfg = LayerTypedConfig(SLOTS * TABLE + 1, BLOCK, TABLE, window=W, chunk=CHUNK, slots=SLOTS)
    return LayerTypedPool(cfg, runner.body.kv_layout())


class _Poison:
    """NaN in every window block the pool has taken back, zeros again when it
    hands one out: a step that READS a released block, masked or not, spoils
    its logits."""

    def __init__(self, pool):
        self.pool, self.bad = pool, set()

    def after_slide(self, seq):
        pool = self.pool
        held = set(pool.window_blocks_of(seq)[1])
        free = set(pool.windowed._free)
        kw, vw = pool.windowed.arrays
        for blocks, value in ((held & self.bad, 0.0), (free - self.bad, jnp.nan)):
            for b in blocks:
                kw, vw = kw.at[:, b].set(value), vw.at[:, b].set(value)
        self.bad = free
        pool.windowed.arrays = (kw, vw)


def _teacher_forced(runner, n_prompt=37, n_out=14, poison=False):
    """Prefill ``n_prompt`` tokens in chunks (the last with a padded tail),
    the window layers' blocks slid before each as the engine slides them, then
    decode the sequence's own next tokens one step at a time in batch row 1,
    beside two dead rows.  Returns (reference logits, engine logits) at the
    chunks' last tokens and at every decode position, and the most window
    blocks the sequence held."""
    seq = _prompt(2, n_prompt + n_out)
    pool = _pool(runner)
    pool.allocate("other", 4)
    pool.allocate("seq", len(seq))
    spoil = _Poison(pool) if poison else None
    rows, got, most = [], [], 0

    def slide(start, n):
        nonlocal most
        pool.slide("seq", start, n)
        if spoil:
            spoil.after_slide("seq")
        most = max(most, len(pool.window_blocks_of("seq")[1]))
        return pool.table_row("seq")

    for pos in range(0, n_prompt, CHUNK):
        piece = seq[pos:min(pos + CHUNK, n_prompt)]
        buf = np.zeros(CHUNK, np.int32)
        buf[:len(piece)] = piece
        *arrays, logits, _, _ = runner.prefill_chunk(
            *pool.arrays, buf, pos, len(piece), slide(pos, len(piece)), GREEDY)
        pool.arrays = arrays
        rows.append(pos + len(piece) - 1)
        got.append(np.asarray(logits))
    step = jax.jit(runner._decode_logits)
    for i in range(n_prompt, n_prompt + n_out):
        tables = np.stack([pool.table_row(None), slide(i, 1), pool.table_row(None)])
        arrays, logits = step(
            runner.params, (*pool.arrays, *runner._counts), np.array([0, seq[i], 0], np.int32),
            np.array([0, i, 0], np.int32), tables)
        pool.arrays = arrays[:4]
        rows.append(i)
        got.append(np.asarray(logits[1]))
    assert pool.audit()["ok"]
    return _reference(seq, rows), np.stack(got), most


# -- the configuration ------------------------------------------------------------------


def test_the_published_layers_5_to_9_are_one_dense_and_a_whole_period():
    cfg = AfmoeConfig()
    assert cfg.layer_types == LAYERS_5_TO_9 and cfg.n_dense_layers == 1
    assert (cfg.n_of("sliding_attention"), cfg.n_of("full_attention")) == (4, 1)
    assert cfg.runs() == (
        ("sliding_attention", "dense", 1), ("sliding_attention", "moe", 1),
        ("full_attention", "moe", 1), ("sliding_attention", "moe", 2))
    layout = cfg.serving_body().kv_layout()
    assert layout["kinds"] == {"full": 1, "window": 4} and layout["window"] == 4096
    assert cfg.cache_kind == "windowed"


def test_the_parameters_by_the_arithmetic_at_the_published_sizes():
    """4.32B parameters: a dense layer, four expert layers of 32 held experts
    beside a shared one, an eighth of the vocabulary twice."""
    cfg = AfmoeConfig()
    shapes = jax.eval_shape(lambda: afmoe_init(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(t))  # noqa: E731
    d, e = 3072, 128
    attention = 3 * d * 48 * e + 2 * d * 8 * e + 2 * e + 4 * d
    assert attention - 2 * e - 4 * d == 62_914_560           # 62.91M a layer, the gate in
    expert = 3 * d * 3072
    dense = attention + 3 * d * 12288
    moe = attention + 33 * expert + d * 256 + 256
    assert count(shapes) == dense + 4 * moe + 2 * 25024 * d + d
    assert 4.32e9 < count(shapes) < 4.33e9
    assert count(shapes["experts"]) == 4 * 32 * expert


@pytest.mark.parametrize("bad", [
    dict(layer_types=("sliding_attention",) * 5),            # no full layer: no pool for it
    dict(layer_types=LAYERS_5_TO_9[:4]),
    dict(n_dense_layers=5),
    dict(n_heads=7),
    dict(window=0),
    dict(expert_offset=240),
])
def test_a_configuration_the_program_cannot_serve_is_refused(bad):
    with pytest.raises(ValueError):
        AfmoeConfig(**bad)


# -- the engine's steps against the reference ---------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_then_decodes_past_the_window_and_several_releases_match_the_reference(impl):
    # five chunks (the last 5 of 8 tokens) and decodes from position 37 on: the
    # window of 12 is passed in the second chunk and a block goes back to the
    # free list every 4 tokens; "pallas": the paged kernel with its lower bound
    # and the batch expert kernel, interpreted
    want, got, most = _teacher_forced(
        _runner(attn_impl=impl), n_out=14 if impl == "xla" else 4, poison=True)
    assert np.isfinite(got).all()
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5 and 0.3 < want.std() < 3.0  # logits of order one
    assert 3 < most <= CAP


def test_poisoned_released_blocks_change_no_logit():
    _, clean, _ = _teacher_forced(_runner())
    _, spoiled, _ = _teacher_forced(_runner(), poison=True)
    assert np.array_equal(clean, spoiled)


class _NoGate(AfmoeBody):
    def _gate(self, att, g):
        return att.astype(jnp.float32).reshape(g.shape)


class _FullTurns(AfmoeBody):
    """Rotary on the full layer too."""

    def _qkv(self, h, layer, positions, turns):
        return super()._qkv(h, layer, positions, True)


class _NoTurns(AfmoeBody):
    def _turn(self, x, positions):
        return x


class _OneNorm(AfmoeBody):
    """No norm after a part (a pre-norm block)."""

    def _norm(self, h, layer, which):
        return h.astype(jnp.float32) if which in ("ln2", "ln4") else super()._norm(h, layer, which)


class _PlainEmbedding(AfmoeBody):
    def embed(self, params, tokens):
        return super().embed(params, tokens) / self.cfg.d_model**0.5


@pytest.mark.parametrize("fault", [
    dict(window=W + BLOCK), dict(window=W - 1), dict(body=_NoGate), dict(body=_FullTurns),
    dict(body=_NoTurns), dict(body=_OneNorm), dict(body=_PlainEmbedding),
    dict(routed_scaling=1.0), dict(expert_offset=0),
], ids=lambda f: next(iter(f.values())).__name__ if "body" in f else str(f))
def test_one_broken_thing_fails(fault):
    # the served window is the POOL's too: it follows the body's layout
    cfg = LayerTypedConfig(SLOTS * TABLE + 1, BLOCK, TABLE, window=fault.get("window", W),
                           chunk=CHUNK, slots=SLOTS)
    runner = _runner(**fault)
    seq = _prompt(2, 40)
    pool = LayerTypedPool(cfg, runner.body.kv_layout())
    pool.allocate("seq", len(seq))
    got = []
    for pos in range(0, 40, CHUNK):
        pool.slide("seq", pos, CHUNK)
        *arrays, logits, _, _ = runner.prefill_chunk(
            *pool.arrays, np.asarray(seq[pos:pos + CHUNK], np.int32), pos, CHUNK,
            pool.table_row("seq"), GREEDY)
        pool.arrays = arrays
        got.append(np.asarray(logits))
    want = _reference(seq, list(range(CHUNK - 1, 40, CHUNK)))
    assert np.abs(want - np.stack(got)).max() > 10 * TOL


def test_a_released_block_that_is_read_spoils_the_logits():
    """The control the poison test rests on: a table whose window row still
    names a block the pool has taken back, inside the walk's reach."""
    runner = _runner()
    seq = _prompt(2, 40)
    pool = _pool(runner)
    pool.allocate("seq", len(seq))
    spoil = _Poison(pool)

    def last_chunk(table):
        *arrays, logits, _, _ = runner.prefill_chunk(
            *pool.arrays, np.asarray(seq[32:40], np.int32), 32, CHUNK, table, GREEDY)
        pool.arrays = arrays
        return np.asarray(logits)

    for pos in range(0, 32, CHUNK):
        pool.slide("seq", pos, CHUNK)
        spoil.after_slide("seq")
        *arrays, _, _, _ = runner.prefill_chunk(
            *pool.arrays, np.asarray(seq[pos:pos + CHUNK], np.int32), pos, CHUNK,
            pool.table_row("seq"), GREEDY)
        pool.arrays = arrays
    pool.slide("seq", 32, CHUNK)
    spoil.after_slide("seq")
    table = pool.table_row("seq")
    assert np.isfinite(last_chunk(table)).all()
    stale = table.copy()
    stale[TABLE + pool.window_blocks_of("seq")[0]] = min(spoil.bad)
    assert not np.isfinite(last_chunk(stale)).all()


# -- through LLMEngine --------------------------------------------------------------------


def _serve(prompts, n_out=10, watch=None, **over):
    eng = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **over)))
    reqs = [eng.submit(p, SamplingParams(max_tokens=n_out)) for p in prompts]
    for _ in range(2000):
        if all(r.finished for r in reqs):
            break
        eng.step()
        if watch:
            watch(eng)
    assert all(r.finished for r in reqs)
    return eng, [list(r.out) for r in reqs]


def _deficits(prompts, outs):
    """The harness's comparison (``benchmark/reference_check.py``): the
    reference's logit of the engine's token against its largest, teacher-forced
    on the engine's own tokens."""
    worst = 0.0
    for prompt, out in zip(prompts, outs):
        rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(out)))
        logits = _reference(prompt + out[:-1], rows)
        worst = max(worst, float((logits.max(-1) - logits[np.arange(len(out)), out]).max()))
    return worst


def test_the_engine_serves_mixed_lengths_past_the_window_and_counts_what_it_holds():
    prompts = [_prompt(5, 5), _prompt(6, 30), _prompt(7, 61), _prompt(8, 44)]
    most = {"window": 0}

    def watch(eng):
        for r in eng.scheduler.slots:
            if r is not None:
                most["window"] = max(most["window"], len(eng.pool.window_blocks_of(r.id)[1]))
        assert eng.pool.audit()["ok"]

    eng, outs = _serve(prompts, n_out=12, watch=watch)
    assert _deficits(prompts, outs) < TOL
    assert 3 < most["window"] <= CAP
    s = eng.stats()
    kv, sp = s["kv_pool"], s["state_pool"]
    assert kv["window"] == W and kv["window_blocks_held"] == kv["full_blocks_held"] == 0
    # every window block claimed went back: behind the window, or at the end
    assert kv["window_blocks_released"] > 20
    assert eng.pool.windowed.num_free_blocks == SLOTS * CAP
    # a decode's rows see min(context, W) in a window layer
    assert 0 < kv["decode_window_tokens"] < kv["decode_tokens"]
    assert kv["decode_window_tokens"] <= W * kv["decode_rows"]
    assert sp["decode_window_tokens"] == kv["decode_window_tokens"]
    assert sp["decode_tokens"] == kv["decode_tokens"] and sp["chunks"] > 0
    assert s["moe"]["decodes"] == kv["decodes"] and s["preemptions"] == 0
    led = s["hbm"]
    assert led["pool_bytes"] == eng.pool.device_bytes and led["window_seq_bytes"] == 0
    assert led["window_bytes"] == eng.pool.windowed.device_bytes


def test_a_preempted_sequence_recomputes_to_the_same_tokens():
    prompts = [_prompt(11, 40), _prompt(12, 36), _prompt(13, 33)]
    _, want = _serve(prompts, n_out=30)
    # 36 usable full blocks of 4: the three prompts fit, their 30 tokens do not
    eng, got = _serve(prompts, n_out=30, num_blocks=37)
    assert eng.stats()["preemptions"] > 0
    assert got == want
    assert eng.pool.audit()["ok"] and eng.pool.windowed.num_free_blocks == SLOTS * CAP


def test_the_prefix_cache_is_refused_with_the_reason():
    with pytest.raises(ValueError, match="keys blocks of ONE kind"):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, prefix_cache=True)))
    for bad in (dict(spec_k=2), dict(tp=2)):
        with pytest.raises(ValueError, match="AfmoeConfig"):
            LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **bad)))


def test_build_llm_app_knows_the_family():
    from ray_tpu.serve.llm import _FAMILIES

    module, cfg, init, default = _FAMILIES["afmoe"]
    assert (module, cfg, init, default) == (
        "ray_tpu.models.afmoe", "AfmoeConfig", "afmoe_init", "AfmoeConfig")


# -- the share of an expert layer ---------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """What the four chips that share a layer each add, the shared expert
    counted once, is what the uncut reference's layer (all 16 experts held)
    adds before its closing norm."""
    whole = dataclasses.replace(TINY, experts_held=16, expert_offset=0, expert_parallel=1)
    params = afmoe_init(jax.random.PRNGKey(3), whole)
    run = next(r for r in params["runs"] if "router" in r)
    layer = jax.tree_util.tree_map(lambda a: a[0], run)
    experts = jax.tree_util.tree_map(lambda a: a[:16], params["experts"])
    h = jax.random.normal(jax.random.PRNGKey(4), (24, TINY.d_model))
    y = reference._rmsnorm(h, layer["ln3"]["scale"], TINY.norm_eps)
    sh = layer["shared"]
    shared = np.asarray(moe.swiglu(y, sh["gate"], sh["up"], sh["down"]))
    chosen, weights = moe.route(
        y, layer["router"]["kernel"], layer["router"]["bias"], TINY.experts_per_tok,
        TINY.routed_scaling, eps=TINY.route_eps)

    def routed(offset, held):
        """The program's routed part for the share ``offset .. offset + held``."""
        mine = jax.tree_util.tree_map(lambda a: a[offset:offset + held], experts)
        mask, wmat = moe.held_pairs(chosen, weights, offset, held, jnp.ones(24, bool))
        return np.asarray(moe.expert_layer(
            y, mask, wmat, mine["gate"], mine["up"], mine["down"],
            top_k=TINY.experts_per_tok, impl="xla"))

    parts = [routed(offset, 4) for offset in range(0, 16, 4)]
    assert all(np.abs(p).max() > 0.05 for p in parts)  # every share has pairs
    # the reference's uncut layer, its closing norm taken off: h' - h = N4(sum),
    # and with a scale of 1 the sum is that times its own rms
    consts = reference._frozen({k: v for k, v in family.reference_sizes(whole).items()
                                if k != "layer_types"})
    with jax.default_matmul_precision("highest"):
        after, _, _ = reference._experts(h, layer, experts, consts)
    total = sum(parts) + shared
    rms = np.sqrt((total * total).mean(-1, keepdims=True) + TINY.norm_eps)
    assert np.abs(np.asarray(after - h) - total / rms).max() < 1e-4
    # and the program's whole layer is the same
    counts = jnp.zeros((len(moe.COUNTERS) + 16,), jnp.int32)
    got, _ = whole.serving_body()._expert_mlp(
        h, layer, jnp.ones(24, bool), counts, "chunk", experts, 0)
    assert np.abs(np.asarray(after) - np.asarray(got)).max() < 1e-4
