"""Granite-4.0-H (Mamba-2 layers with an attention layer without positional
encoding among them, an expert layer closing EVERY layer) through the engine
against its plain reference.

The reference (``benchmark/reference/granite_h.py``) is the equations over
the whole sequence in float32: a token loop for the recurrence, a dense masked
softmax, a loop over the held experts, no cache.  The engine serves chunks
(the SSD chunk form in sub-chunks, a walk over the block table, tiles of
pairs), then decodes through the caches SPLIT BY LAYER KIND: a slot of state
in the Mamba layers, paged K/V in the attention layer.  Every comparison holds
one to the other on LOGITS, at a small size on the CPU in float32: four
layers in three runs (two Mamba, one attention, one Mamba), 8 query heads on
2 key-value heads, 4 SSM heads in one group, sub-chunks of 4 inside chunks of
8, experts 4-7 of 8 held, 3 a token.

``TOL``: float32 round-off of two summation orders reads about 2e-6 on logits
of size 1; each named fault of the program reads 2e-2 and more
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

from benchmark.families import granite_h as family  # noqa: E402
from benchmark.reference import granite_h as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import HybridConfig, HybridPool  # noqa: E402
from ray_tpu.llm.model_runner import host_batch, pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.blocks import Mamba2  # noqa: E402
from ray_tpu.models.granite_h import (  # noqa: E402
    PERIOD,
    GraniteHBody,
    GraniteHConfig,
    granite_h_init,
)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.moe import COUNTERS  # noqa: E402
from ray_tpu.ops.gqa_attention import rotary_half  # noqa: E402

TOL = 1e-3
TINY = GraniteHConfig(
    vocab_size=192, d_model=64, n_layers=4,
    layer_types=("mamba", "mamba", "attention", "mamba"), n_heads=8, n_kv_heads=2, head_dim=8,
    d_ssm=64, ssm_heads=4, d_state=16, ssm_chunk=4, d_expert=16, d_shared=32,
    n_routed_experts=8, experts_held=4, expert_offset=4, experts_per_tok=3,
    # logits of order one at this width: sqrt(64) / (4 * 2)
    embedding_multiplier=4.0, logits_scaling=2.0, attention_multiplier=0.125, init_range=0.25,
    dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    return granite_h_init(jax.random.PRNGKey(0), TINY)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TINY.vocab_size, n)]


def _reference(tokens, rows):
    return np.asarray(family.reference_logits(_params(), tokens, rows, TINY))


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


def _teacher_forced(runner, n_prompt=21, n_out=30, fill=0.0):
    """Prefill ``n_prompt`` tokens in chunks (the last with a padded tail),
    then decode the sequence's own next tokens one step at a time in batch
    row 1, beside two dead rows.  Returns (reference logits, engine logits)
    at the chunks' last tokens and at every decode position."""
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
    arrays = (*pool.arrays, *runner._counts)
    for i in range(n_prompt, n_prompt + n_out):
        tokens = np.array([0, seq[i], 0], np.int32)
        positions = np.array([0, i, 0], np.int32)
        arrays, logits = step(runner.params, arrays, tokens, positions, tables)
        rows.append(i)
        got.append(np.asarray(logits[1]))
    return _reference(seq, rows), np.stack(got)


# -- the engine's steps against the reference ---------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_with_a_padded_tail_then_decodes_through_both_caches_match_the_reference(impl):
    # three chunks of two sub-chunks each (the last chunk 5 of 8 tokens), then
    # decodes through the slot of state of three layers and the paged K/V of
    # one; "pallas": the SSD decode kernel and the paged kernel with 4 query
    # heads a key-value head on its window axis, interpreted
    want, got = _teacher_forced(_runner(attn_impl=impl), n_out=30 if impl == "xla" else 6,
                                fill=3.0)
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5 and 0.3 < want.std() < 2.0  # logits of order one


# One departure from the equations a case, planted HERE by overriding one
# small method of the body or one field of the configuration: the served
# programs hold no such switch.


class _Rotary(GraniteHBody):
    """``position_embedding_type`` read as rotary: q and k turned at the
    token's position."""

    def decode(self, params, x, arrays, positions, tables):
        self._positions = positions
        return super().decode(params, x, arrays, positions, tables)

    def chunk(self, params, x, arrays, start, n_valid, table):
        self._positions = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        return super().chunk(params, x, arrays, start, n_valid, table)

    def _qkv(self, u, layer):
        q, k, v = super()._qkv(u, layer)
        return rotary_half(q, self._positions, 10000.0), rotary_half(k, self._positions, 10000.0), v


class _ScaleRootOfTheHead(GraniteHBody):
    """A softmax scale of ``head_dim ** -0.5`` and not ``attention_multiplier``."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.q_scale = 1.0


class _NormBeforeGate(GraniteHBody):
    """RMSNorm(y) . silu(z) and not RMSNorm(y . silu(z)): planted in the
    shared mixer (``models.blocks.Mamba2``) this body holds."""

    class Mixer(Mamba2):
        def out(self, y, z, layer):
            g = y.reshape(z.shape)
            g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + self.eps)
            return jnp.dot(g * layer["ssm_norm"]["scale"] * jax.nn.silu(z),
                           layer["ssm_out"]["kernel"])

    def __init__(self, cfg):
        super().__init__(cfg)
        self.ssm = self.Mixer(**{f.name: getattr(self.ssm, f.name)
                                 for f in dataclasses.fields(Mamba2)})


@pytest.mark.parametrize("broken", [
    dict(residual_multiplier=1.0), dict(logits_scaling=1.0), dict(embedding_multiplier=1.0),
    _Rotary, _ScaleRootOfTheHead, _NormBeforeGate],
    ids=lambda b: b.__name__ if isinstance(b, type) else next(iter(b)))
def test_one_broken_thing_fails(broken):
    if isinstance(broken, dict):
        cfg = dataclasses.replace(TINY, **broken)
    else:
        class Config(GraniteHConfig):
            def serving_body(self):
                return broken(self)

        cfg = Config(**dataclasses.asdict(TINY))
    runner = HybridModelRunner(cfg, _params(), block_size=BLOCK)
    want, got = _teacher_forced(runner, n_out=6)
    assert np.abs(want - got).max() > 10 * TOL


def test_a_bfloat16_ssd_state_fails_the_tolerance():
    want, got = _teacher_forced(_runner(state_dtype="bfloat16"))
    assert np.abs(want - got).max() > TOL


# -- the expert layer ---------------------------------------------------------------------


def _layer(seed=5, n=21, d=16, f=8, experts=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (n, d)),
        router=jax.random.normal(ks[1], (d, experts)) * d**-0.5,
        gate=jax.random.normal(ks[2], (experts, d, f)) * d**-0.5,
        up=jax.random.normal(ks[3], (experts, d, f)) * d**-0.5,
        down=jax.random.normal(ks[4], (experts, f, d)) * f**-0.5)


def _share(lay, offset, held, top_k=4, tile=64):
    """The routed part one chip holding ``held`` experts from ``offset`` adds."""
    chosen, weights = moe.route_logits(lay["x"], lay["router"], top_k)
    mask, wmat = moe.held_pairs(chosen, weights, offset, held, jnp.ones(lay["x"].shape[0], bool))
    cut = lambda k: lay[k][offset:offset + held]  # noqa: E731
    return moe.expert_layer(lay["x"], mask, wmat, cut("gate"), cut("up"), cut("down"),
                            top_k=top_k, tile=tile), mask


def test_the_router_is_a_softmax_over_the_chosen_logits():
    """By hand, and a case where sigmoid-then-normalise (``ops.moe.route``)
    gives other weights: logits 4, 2, 0 are 0.867, 0.117, 0.016 under the
    softmax and 0.416, 0.373, 0.211 as normalised sigmoids."""
    x = jnp.eye(4, dtype=jnp.float32)[:1]
    kernel = jnp.zeros((4, 6)).at[0].set(jnp.array([0.0, 4.0, -3.0, 2.0, -1.0, -5.0]))
    chosen, weights = moe.route_logits(x, kernel, 3)
    assert chosen.tolist() == [[1, 3, 0]]
    want = np.exp([4.0, 2.0, 0.0]) / np.exp([4.0, 2.0, 0.0]).sum()
    np.testing.assert_allclose(weights[0], want, rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    other, w_sigmoid = moe.route(x, kernel, jnp.zeros(6), 3, 1.0)
    assert other.tolist() == chosen.tolist()
    assert np.abs(np.asarray(w_sigmoid[0]) - want).max() > 0.2
    # on random logits: the choice is the largest logits', the weights theirs alone
    lay = _layer()
    chosen, weights = moe.route_logits(lay["x"], lay["router"], 4)
    z = np.asarray(jnp.dot(lay["x"], lay["router"], precision="highest"))
    assert (np.sort(np.asarray(chosen), -1) == np.sort(np.argsort(-z, -1)[:, :4], -1)).all()
    picked = np.take_along_axis(z, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, np.exp(picked) / np.exp(picked).sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("shares,tile", [(2, 64), (4, 3)])
def test_the_shares_add_up_to_the_uncut_layer(shares, tile):
    lay = _layer()
    held = 16 // shares
    total = sum(_share(lay, s * held, held, tile=tile)[0] for s in range(shares))
    whole, mask = _share(lay, 0, 16)
    assert int(mask.sum()) == 21 * 4
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 1e-5


def test_the_two_shares_and_the_shared_mlp_once_equal_the_uncut_reference_layer():
    """Guide section 4: the parts that both shares give (offsets 0 and 4 of
    8 here, 0 and 36 of 72 as published), with what every chip computes
    alike (the shared MLP, the residual) counted ONCE, add up to what the
    uncut reference gives for the whole layer; and the program's layer, one
    share, is the reference's same share."""
    uncut = dataclasses.replace(TINY, experts_held=8, expert_offset=0, expert_parallel=1)
    full = granite_h_init(jax.random.PRNGKey(1), uncut)
    layer = jax.tree_util.tree_map(lambda a: a[0], full["runs"][0])
    experts = jax.tree_util.tree_map(lambda a: a[:8], full["experts"])
    h = jax.random.normal(jax.random.PRNGKey(2), (13, TINY.d_model))
    cut = lambda o: jax.tree_util.tree_map(lambda a: a[o:o + 4], experts)  # noqa: E731
    consts = lambda o: reference._frozen(family.reference_sizes(  # noqa: E731
        dataclasses.replace(TINY, expert_offset=o)))
    with jax.default_matmul_precision("highest"):
        whole = reference._experts(h, layer, experts, consts(0))[0]
        parts = [reference._experts(h, layer, cut(o), consts(o))[0] for o in (0, 4)]
        y = reference._rmsnorm(h, layer["ln2"]["scale"], TINY.rms_norm_eps)
        once = h + TINY.residual_multiplier * reference._swiglu(
            y, *(layer["shared"][k] for k in ("gate", "up", "down")))
    assert np.abs(np.asarray(sum(parts) - once) - np.asarray(whole)).max() < 1e-5
    assert np.abs(np.asarray(parts[1]) - np.asarray(whole)).max() > 1e-3
    body = TINY.serving_body()  # experts 4-7
    counts = jnp.zeros(len(COUNTERS) + 4, jnp.int32)
    got, counts = body._expert_mlp(h, layer, jnp.ones(13, bool), counts, "decode", cut(4), 0)
    assert np.abs(np.asarray(got) - np.asarray(parts[1])).max() < 1e-5
    assert int(counts[0]) == int(counts[len(COUNTERS):].sum()) > 0


@pytest.mark.parametrize("tile", [64, 4])
def test_no_pair_is_dropped_when_every_row_chooses_one_expert(tile):
    lay = _layer()
    # a router under which every row's largest logit is expert 5's
    x = jnp.abs(lay["x"])
    lay = dict(lay, x=x, router=lay["router"].at[:, 5].set(10.0))
    out, mask = _share(lay, 4, 4, tile=tile)
    assert mask[:, 1].all() and int(mask[:, 1].sum()) == 21
    singly = sum(_share(lay, e, 1)[0] for e in range(4, 8))
    assert np.abs(np.asarray(out) - np.asarray(singly)).max() < 1e-5
    # expert 5 alone, by hand: every row, with the weight the router gave it
    chosen, weights = moe.route_logits(x, lay["router"], 4)
    w5 = (weights * (chosen == 5)).sum(-1, keepdims=True)
    assert (np.asarray(w5) > 0.5).all()
    with jax.default_matmul_precision("highest"):
        want = w5 * moe.swiglu(x, lay["gate"][5], lay["up"][5], lay["down"][5])
    assert np.abs(np.asarray(_share(lay, 5, 1, tile=tile)[0]) - np.asarray(want)).max() < 1e-5


def test_a_dead_row_has_no_pair():
    lay = _layer()
    chosen, weights = moe.route_logits(lay["x"], lay["router"], 4)
    live = jnp.arange(21) % 2 == 0
    mask, wmat = moe.held_pairs(chosen, weights, 0, 16, live)
    assert int(mask.sum()) == 4 * 11 and not mask[1::2].any() and not wmat[1::2].any()


@pytest.mark.parametrize("load,n,rows", [
    ([0, 0, 0], 16, 0),            # no pair, no row
    ([1, 0, 3], 16, 32),           # the batch form: a touched expert sees the batch's 16 rows
    ([16, 2, 0], 16, 32),
    ([70, 1, 64], 512, 3 * 128),   # a chunk, the grouped form: an expert's pairs in blocks of 128
    ([130, 0, 256], 512, 4 * 128),  # two blocks for 130 pairs and for 256, none for none
    ([70, 1, 64], 72, 3 * 80),     # fewer rows than a block of 128: all 72, in whole tiles of 16
])
def test_the_tile_loops_rows_by_hand(load, n, rows):
    assert int(moe.tile_rows(jnp.asarray(load, jnp.int32), n)) == rows


# -- the batch form: a batch of no more rows than a tile makes no tile ---------------------


@pytest.mark.parametrize("load,n,steps", [
    ([0, 0, 0], 16, 0),            # no expert touched, no step
    ([1, 0, 3], 16, 2),            # a step a touched expert, whatever it holds
    ([16, 2, 1], moe.TILE, 3),     # a batch of a tile's rows is still the batch form
    ([16, 2, 1], moe.TILE + 1, 0),  # one row more: the grouped form, which counts none here
    ([70, 1, 64], 512, 0),
])
def test_the_batch_forms_steps_by_hand(load, n, steps):
    assert int(moe.batch_steps(jnp.asarray(load, jnp.int32), n)) == steps


@pytest.mark.parametrize("load,n,steps", [
    ([0, 0, 0], 512, 0),           # no expert touched, no step
    ([70, 0, 300], 512, 2),        # ONE step a touched expert, however many blocks its pairs fill
    ([16, 2, 1], moe.TILE + 1, 3),
    ([16, 2, 1], moe.TILE, 0),     # a tile's rows: the batch form, which counts none here
])
def test_the_grouped_forms_steps_by_hand(load, n, steps):
    assert int(moe.grouped_steps(jnp.asarray(load, jnp.int32), n)) == steps


@pytest.mark.parametrize("top_k,places", [(2, 10), (4, 20), (None, 20), (9, 20)])
def test_the_pairs_in_expert_order_by_hand(top_k, places):
    """5 rows over 4 experts, two pairs a row at most: expert 0 has rows 1 and
    4, expert 1 none, expert 2 rows 0, 1 and 3, expert 3 row 3; row 2 is dead.
    The bound is rows x min(top_k, experts) places, and no sort makes them."""
    mask = jnp.asarray([[0, 0, 1, 0], [1, 0, 1, 0], [0, 0, 0, 0], [0, 0, 1, 1], [1, 0, 0, 0]], bool)
    wmat = jnp.where(mask, jnp.arange(20, dtype=jnp.float32).reshape(5, 4) + 1, 0.0)
    starts, counts, token, weight = moe.expert_order(mask, wmat, top_k)
    assert starts.tolist() == [0, 2, 2, 5] and counts.tolist() == [2, 0, 3, 1]
    assert token.shape == weight.shape == (places,)
    assert token[:6].tolist() == [1, 4, 0, 1, 3, 3] and not token[6:].any()
    assert weight[:6].tolist() == [5.0, 17.0, 3.0, 7.0, 15.0, 16.0] and not weight[6:].any()
    text = str(jax.make_jaxpr(lambda m, w: moe.expert_order(m, w, top_k))(mask, wmat))
    assert "sort" not in text and "scatter" not in text


@pytest.mark.parametrize("hit,ids,count", [
    ([0, 0, 0, 0], [0, 0, 0, 0], 0),          # nothing touched: every step names expert 0
    ([0, 1, 0, 1], [1, 3, 3, 3], 2),          # past the count: the last touched, again
    ([1, 1, 1, 1], [0, 1, 2, 3], 4),
    ([0, 0, 1, 0], [2, 2, 2, 2], 1),
])
def test_the_touched_experts_are_listed_in_order_without_a_sort(hit, ids, count):
    mask = jnp.zeros((5, 4), bool).at[2].set(jnp.asarray(hit, bool))
    got, n = moe.touched(mask)
    assert got.tolist() == ids and int(n) == count
    assert "sort" not in str(jax.make_jaxpr(moe.touched)(mask))


@pytest.mark.parametrize("d,f,size,bf", [
    (4096, 768, 2, 768),      # granite-4.0-h-small: 18.9 MB an expert, whole
    (7168, 2048, 2, 512),     # kimi-k2.5: 88 MB an expert, a quarter of f a step
    (7168, 2048, 4, 256),
    (64, 16, 4, 16),
])
def test_the_kernels_blocks_follow_the_shape_and_the_budget(d, f, size, bf):
    assert moe.block_f(d, f, size) == bf
    assert 2 * 3 * d * bf * size <= moe.VMEM_BUDGET
    with pytest.raises(ValueError, match="no block"):
        moe.block_f(d, f, size, budget=6 * d * min(f, 128) * size - 1)


def _by_hand(case, n, experts=6, seed=3):
    """A load set by hand over ``n`` rows and 6 held experts: (mask, wmat)."""
    rng = np.random.default_rng(seed)
    wmat = rng.uniform(0.1, 1.0, (n, experts)).astype(np.float32)
    mask = np.zeros((n, experts), bool)
    if case == "every_row_one_expert":
        mask[:, 4] = True
    elif case == "a_dead_row":            # row 0 has no pair; the rest two or three each
        mask[1:] = rng.random((n - 1, experts)) < 0.4
        mask[1:, 1] = True
    elif case == "scattered":
        mask[:] = rng.random((n, experts)) < 0.3
    else:
        assert case == "no_row_any_expert"
    return jnp.asarray(mask), jnp.asarray(np.where(mask, wmat, 0.0))


def _dense(lay, mask, wmat, first):
    """The uncut sum: every held expert on every row, the weight 0 where the
    row did not choose it."""
    with jax.default_matmul_precision("highest"):
        return sum(wmat[:, e:e + 1] * moe.swiglu(
            lay["x"], lay["gate"][first + e], lay["up"][first + e], lay["down"][first + e])
            for e in range(mask.shape[1]))


def _through_the_grouped_form(lay, mask, wmat, first, impl):
    """The same rows with one dead row appended, so that they are one more
    than the tile: the grouped form, whatever ``n`` is."""
    n = mask.shape[0]
    pad = lambda a: jnp.concatenate([a, jnp.zeros_like(a[:1])])  # noqa: E731
    return moe.expert_layer(pad(lay["x"]), pad(mask), pad(wmat), lay["gate"], lay["up"],
                            lay["down"], first=first, tile=n, impl=impl)[:n]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case,n", [
    ("every_row_one_expert", 16), ("no_row_any_expert", 16), ("a_dead_row", 16),
    ("scattered", 1), ("scattered", 5), ("scattered", 16),
])
def test_the_batch_form_equals_the_grouped_form_and_the_uncut_sum(case, n, impl):
    lay = _layer(n=n, experts=10)        # the 6 held experts lie at 3..8 of the flat array
    mask, wmat = _by_hand(case, n)
    got = moe.expert_layer(lay["x"], mask, wmat, lay["gate"], lay["up"], lay["down"],
                           first=3, impl=impl)
    assert got.shape == lay["x"].shape and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - np.asarray(_dense(lay, mask, wmat, 3))).max() < 1e-5
    grouped = _through_the_grouped_form(lay, mask, wmat, 3, impl)
    assert np.abs(np.asarray(got) - np.asarray(grouped)).max() < 1e-5
    if case == "no_row_any_expert":
        assert not np.asarray(got).any()
    if case == "a_dead_row":
        assert not np.asarray(got[0]).any() and np.asarray(got[1]).any()


@pytest.mark.parametrize("d,f,bf,dtype", [
    (256, 384, None, "bfloat16"),    # granite-4.0-h-small's 4096 x 768, a sixteenth: whole
    (448, 256, 128, "bfloat16"),     # kimi-k2.5's 7168 x 2048: f cut in blocks
    (448, 256, 128, "float32"),
    (64, 48, 16, "float32"),
], ids=["granite_h", "kimi", "kimi_f32", "three_blocks"])
def test_the_kernel_in_interpret_mode_equals_the_plain_batch_form(d, f, bf, dtype):
    lay = {k: v.astype(dtype) if k != "router" else v
           for k, v in _layer(n=16, d=d, f=f, experts=12).items()}
    mask, wmat = _by_hand("scattered", 16, experts=5, seed=8)
    ids, count = moe.touched(mask)
    assert 2 <= int(count) <= 5
    args = (lay["x"], jnp.where(mask, wmat, 0.0).T, ids, count,
            (lay["gate"], lay["up"], lay["down"]), 7)
    plain = moe._batch_xla(*args)
    kernel = moe._batch_pallas(*args, interpret=True, bf=bf)
    # the same products on the same dtype; cut blocks add the down product's
    # partial sums in float32 in another order
    assert np.abs(np.asarray(kernel) - np.asarray(plain)).max() < 1e-5 * np.abs(
        np.asarray(plain)).max()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n", [16, 70], ids=["batch_form", "grouped_form"])
def test_a_rows_result_is_bit_equal_whatever_the_other_rows_chose(impl, n):
    lay = _layer(n=n, experts=6)
    mine = jnp.asarray([False, True, False, True, True, False])
    w = jnp.asarray(np.random.default_rng(1).uniform(0.1, 1.0, (n, 6)).astype(np.float32))
    outs = []
    for others in ("none", "all", "some", "permuted", "other_experts"):
        some = _by_hand("scattered", n)[0]
        mask = {"none": jnp.zeros((n, 6), bool), "all": jnp.ones((n, 6), bool), "some": some,
                "permuted": some[::-1],
                "other_experts": jnp.broadcast_to(~mine, (n, 6))}[others].at[3].set(mine)
        out = moe.expert_layer(lay["x"], mask, jnp.where(mask, w, 0.0), lay["gate"], lay["up"],
                               lay["down"], impl=impl)
        outs.append(np.asarray(out[3]))
    assert outs[0].any() and all((o == outs[0]).all() for o in outs[1:])


# -- the grouped form: more rows than a tile, every touched expert sees ITS pairs ---------


def _grouped_case(case):
    """(layer, mask, wmat, first, top_k): loads set by hand over 6 held
    experts of a flat array of 18 (three layers' worth)."""
    n = {"an_expert_takes_every_row": 150, "rows_no_multiple_of_the_block": 131,
         "dead_rows_and_a_padded_tail": 80}.get(case, 70)
    f = 48 if case == "f_in_blocks" else 8
    lay = _layer(n=n, f=f, experts=18)
    first, top_k = 6, None
    rng = np.random.default_rng(11)
    wmat = rng.uniform(0.1, 1.0, (n, 6)).astype(np.float32)
    mask = rng.random((n, 6)) < 0.3
    if case == "an_expert_with_no_pair":
        mask[:, 2] = False
    elif case == "an_expert_takes_every_row":       # two blocks: 128 rows and 22
        mask[:, 4] = True
    elif case == "rows_no_multiple_of_the_block":   # 131 rows, half of them an expert
        mask = rng.random((n, 6)) < 0.5
    elif case == "dead_rows_and_a_padded_tail":     # 61 valid rows of 80, every 7th dead
        mask[(np.arange(n) % 7 == 0) | (np.arange(n) >= 61)] = False
    elif case == "a_traced_first":                  # the third layer's experts
        first = jnp.int32(12)
    elif case == "top_k_is_held":                   # some rows choose all six
        top_k = 6
        mask[::3] = True
    elif case == "top_k_of_the_router":             # three a row, as a router gives them
        top_k = 3
        mask = np.zeros((n, 6), bool)
        np.put_along_axis(mask, rng.permuted(np.tile(np.arange(6), (n, 1)), axis=1)[:, :3], True, 1)
    elif case == "no_row_any_expert":
        mask[:] = False
    else:
        assert case in ("f_in_blocks", "bfloat16")
    if case == "bfloat16":
        lay = {k: v.astype(jnp.bfloat16) for k, v in lay.items()}
    return lay, jnp.asarray(mask), jnp.asarray(np.where(mask, wmat, 0.0)), first, top_k


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", [
    "an_expert_with_no_pair", "an_expert_takes_every_row", "rows_no_multiple_of_the_block",
    "dead_rows_and_a_padded_tail", "a_traced_first", "f_in_blocks", "top_k_is_held",
    "top_k_of_the_router", "no_row_any_expert", "bfloat16"])
def test_the_grouped_form_equals_the_uncut_masked_sum(case, impl, monkeypatch):
    """Every (row, held expert) pair of the mask and no other, whatever the
    load's shape: against every held expert on every row, the weight 0 where
    the row did not choose it.  The kernel runs under the interpreter."""
    lay, mask, wmat, first, top_k = _grouped_case(case)
    if case == "f_in_blocks":                       # three blocks of f a touched expert
        monkeypatch.setattr(moe, "block_f", lambda d, f, size, **_: 16)
    layer = jax.jit(lambda x, m, w, g, u, dn, first: moe.expert_layer(
        x, m, w, g, u, dn, first=first, top_k=top_k, impl=impl))
    got = layer(lay["x"], mask, wmat, lay["gate"], lay["up"], lay["down"], first)
    assert got.shape == lay["x"].shape and got.dtype == jnp.float32
    want = np.asarray(_dense(lay, mask, wmat, int(first)))
    assert np.abs(np.asarray(got) - want).max() < (2e-2 if case == "bfloat16" else 1e-5)
    if case == "no_row_any_expert":
        assert not np.asarray(got).any()
    if case == "dead_rows_and_a_padded_tail":
        assert not np.asarray(got[61:]).any() and not np.asarray(got[::7]).any()
        assert np.asarray(got[1]).any()


def _primitives(n, impl):
    lay = _layer(n=n, experts=6)
    mask, wmat = _by_hand("scattered", n)
    text = str(jax.make_jaxpr(lambda *a: moe.expert_layer(*a, impl=impl))(
        lay["x"], mask, wmat, lay["gate"], lay["up"], lay["down"]))
    # ``sort[``: the primitive, not a gather's ``indices_are_sorted``
    return {name.strip("[") for name in ("pallas_call", "sort[", "scatter-add", "gather")
            if name in text}


def test_the_rows_choose_the_form_and_nothing_else_does():
    # one row more than a tile: the grouped form, which sorts nothing; its
    # kernel gathers and scatters in VMEM, its plain rendering a block at a time
    assert _primitives(moe.TILE + 1, "pallas") == {"pallas_call"}
    assert _primitives(moe.TILE + 1, "xla") == {"scatter-add", "gather"}
    assert _primitives(moe.TILE + 1, "auto") == {"scatter-add", "gather"}
    # a tile's rows: the batch form, which sorts, gathers and scatters nothing
    assert _primitives(moe.TILE, "pallas") == {"pallas_call"}
    assert _primitives(moe.TILE, "xla") == set() == _primitives(moe.TILE, "auto")
    assert _primitives(16, "pallas") == {"pallas_call"}
    with pytest.raises(ValueError, match="unknown expert impl"):
        _primitives(16, "mosaic")


# -- in place ---------------------------------------------------------------------------


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_pools_and_states_are_updated_in_place(step):
    """No pool-sized temporary in either step, through three layer loops: at
    pools made large against the model, the compiled program's temporaries
    stay under a part of them and every pool is aliased to its output."""
    runner = _runner()
    rows, i32 = 4, np.int32
    pool = _pool(runner, slots=64)
    pools = sum(a.nbytes for a in pool.arrays)
    if step == "decode":
        z = np.zeros(rows)
        ops = host_batch(z.astype(i32), z.astype(i32), np.zeros((rows, 1 + TABLE), i32),
                         z, z, np.ones(rows), z, z)
        lowered = runner._decode.lower(runner.params, *pool.arrays, *runner._counts, *ops)
    else:
        lowered = runner._prefill.lower(
            runner.params, *pool.arrays, *runner._counts, np.zeros(CHUNK, i32), i32(0),
            i32(CHUNK), np.zeros(1 + TABLE, i32), GREEDY, chunk=CHUNK)
    mem = lowered.compile().memory_analysis()
    # prefill: this CPU backend lays the state carries out anew ONCE for the
    # products that read one slot of them, as it does Falcon-H1's
    bound = 0.25 if step == "decode" else 0.75
    assert mem.temp_size_in_bytes < bound * pools, (mem.temp_size_in_bytes, pools)
    assert mem.alias_size_in_bytes >= pools


# -- the two-ledger pool with the caches split by layer kind ---------------------------


@pytest.mark.parametrize("periods,kv_layers,state_layers", [(1, 1, 9), (2, 2, 18)])
def test_the_ledger_splits_the_caches_by_layer_kind(periods, kv_layers, state_layers):
    cfg = dataclasses.replace(TINY, n_layers=10 * periods, layer_types=PERIOD * periods)
    assert cfg.runs() == {
        1: (("mamba", 5), ("attention", 1), ("mamba", 4)),
        2: (("mamba", 5), ("attention", 1), ("mamba", 9), ("attention", 1), ("mamba", 4)),
    }[periods]
    body = cfg.serving_body()
    assert body.kv_layout()["n_layers"] == kv_layers
    pool = HybridPool(HybridConfig(41, BLOCK, TABLE, 2), body.kv_layout(),
                      body.state_leaves(BLOCK))
    k, v, conv, ssd = pool.arrays
    assert k.shape == v.shape == (kv_layers, 41, 2, BLOCK, 8)
    assert conv.shape == (state_layers, 3, 3 * (64 + 2 * 16))
    assert ssd.shape == (state_layers, 3, 4, 16, 16)
    # a block's bytes are its rows in the ATTENTION layers alone, K and V
    assert pool.block_bytes == kv_layers * 2 * (2 * BLOCK * 8 * 4)
    assert pool.states.leaf_bytes() == {"conv": conv.nbytes, "ssd": ssd.nbytes}
    assert pool.states.block_bytes == (conv.nbytes + ssd.nbytes) // 3
    assert len(pool.allocate("a", 100)) == 25 and pool.states.blocks_of("a")[0] in (1, 2)
    assert not pool.can_allocate(64)  # blocks short (15 free, 16 asked), a slot free
    pool.allocate("b", 8)
    assert not pool.can_allocate(4)  # slots short, blocks free
    counts, audit = pool.ledger_counts(), pool.audit()
    assert counts["seq_owned"] == 27 and counts["slots_owned"] == 2
    assert audit["ok"] and sorted(audit["owners"]) == ["a", "b"]
    assert pool.free("b") == 2 and pool.free("a") == 25 and pool.audit()["free"] == 40
    shapes = jax.eval_shape(lambda: granite_h_init(jax.random.PRNGKey(0), cfg))
    assert [r["ln1"]["scale"].shape[0] for r in shapes["runs"]] == [n for _, n in cfg.runs()]
    assert shapes["experts"]["gate"].shape == (10 * periods * 4, 64, 16)


def test_a_layer_pattern_must_name_every_layer_and_both_kinds():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, n_layers=5)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=("mamba",) * 4)
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(TINY, expert_offset=6)


# -- the served path ---------------------------------------------------------------------


def _drive(eng, reqs):
    while not all(r.finished for r in reqs):
        eng.step()
    return [list(r.out) for r in reqs]


@pytest.fixture(scope="module")
def engine():
    """ONE roomy engine at ``ENGINE``'s sizes for the cases that only serve
    through it, its programs compiled once a module.  Its counters only grow:
    a case reads what ITS requests added."""
    return LLMEngine(TINY, _params(), EngineConfig(**ENGINE))


def test_the_served_path_preempted_and_resumed_matches_the_reference(engine):
    """``LLMEngine`` itself, several requests side by side over several
    chunks each.  Few blocks: sequences growing past them are preempted
    (recompute: the next first chunk overwrites a slot) and must give the
    tokens of an engine that never preempts; every token served lies within
    ``TOL`` of the reference's largest logit at its position."""
    prompts = [_prompt(30 + i, 12 + 5 * i) for i in range(4)]
    outs = []
    tight = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, num_blocks=26)))
    for blocks, eng in ((SLOTS * TABLE + 1, engine), (26, tight)):
        assert eng.cfg.num_blocks == blocks
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


def test_stats_moe_and_both_pools_count_what_a_hand_count_gives():
    eng = LLMEngine(TINY, _params(), EngineConfig(**ENGINE))
    prompt, n_out = _prompt(40, 19), 9
    out = eng.generate(prompt, SamplingParams(max_tokens=n_out))
    got = eng.stats()
    moe_n, kv_n, state_n = got["moe"], got["kv_pool"], got["state_pool"]
    # by hand: the reference's own choice at every token the programs were fed
    seq = prompt + out[:-1]
    _, held, _, _ = reference.forward(_params(), seq, family.reference_sizes(TINY))
    held = np.stack([np.asarray(m) for m in held])              # (layers, tokens, held)
    by_chunks, by_decodes = held[:, :len(prompt)], held[:, len(prompt):]
    assert moe_n["chunks"] == 3 and moe_n["decodes"] == kv_n["decodes"] == n_out - 1
    assert moe_n["chunk_pairs"] == by_chunks.sum()
    assert moe_n["decode_pairs"] == by_decodes.sum()
    # a decode of ONE live row touches as many held experts as it has pairs,
    # and each touched expert is one tile of the batch's rows
    assert moe_n["decode_touched"] == moe_n["decode_pairs"]
    assert moe_n["decode_tile_rows"] == moe_n["decode_touched"] * SLOTS
    # every one of them through the batch form (3 rows are no more than a tile)
    assert moe_n["decode_expert_steps"] == moe_n["decode_touched"] > 0
    assert moe_n["load"] == [int(x) for x in held.sum(axis=(0, 1))]
    # the decodes' occupancy: the same three counts under both pools
    for pool_n in (kv_n, state_n):
        assert pool_n["decodes"] == pool_n["decode_rows"] == n_out - 1
        assert pool_n["decode_tokens"] == sum(range(len(prompt) + 1, len(prompt) + n_out))
    assert kv_n["block_tokens"] == BLOCK and kv_n["blocks"] == SLOTS * TABLE
    assert kv_n["bytes"] == eng.pool.kv.device_bytes  # ONE layer's K and V
    assert set(state_n["kinds"]) == {"conv", "ssd"} and state_n["slots"] == SLOTS
    assert state_n["chunks"] == 3 and state_n["chunk_tokens"] == len(prompt)


def test_chunks_alone_count_no_step_of_the_batch_form(engine):
    """``decode_expert_steps`` is the decodes': a request that ends with its
    prompt's last chunk has made none, whatever form its chunks took."""
    eng = engine
    before = eng.stats()["moe"]
    eng.generate(_prompt(41, 19), SamplingParams(max_tokens=1))
    moe_n = {name: n - before[name] for name, n in eng.stats()["moe"].items() if name != "load"}
    assert moe_n["chunks"] == 3 and moe_n["chunk_pairs"] > 0
    assert moe_n["decodes"] == moe_n["decode_touched"] == moe_n["decode_expert_steps"] == 0


@pytest.mark.parametrize("knob,why", [
    (dict(prefix_cache=True), "GraniteHConfig: the radix prefix cache shares blocks"),
    (dict(prefix_cache=True), "a recurrent state beside one layer's keys and values"),
    (dict(prefix_cache=False, spec_k=2), "GraniteHConfig: verifying k drafted tokens"),
    (dict(prefix_cache=False, tp=2), "GraniteHConfig: tensor parallelism"),
    (dict(prefix_cache=False, tp=2), "no sharded form"),
])
def test_the_engine_refuses_what_a_state_cannot_do_and_names_the_family(knob, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **knob)))


def test_the_family_is_found_by_name_at_the_published_widths():
    from benchmark import harness as H
    from ray_tpu.serve.llm import _build_model, build_llm_app

    config = H.load_config(H.manifest(), "granite-4.0-h-small-ep2-l10-1chip")
    assert sorted(config["reduced"]) == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    cfg = H.family_piece(config, "model_config")(H.sizes(config, False))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size, cfg.n_layers,
            cfg.d_ssm, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state, cfg.n_groups, cfg.d_conv,
            cfg.ssm_chunk, cfg.d_expert, cfg.d_shared, cfg.n_routed_experts, cfg.experts_held,
            cfg.experts_per_tok, cfg.expert_parallel) == (
                4096, 32, 8, 128, 50176, 10, 8192, 128, 64, 128, 1, 4, 256, 768, 1536, 72, 36,
                10, 2)
    assert cfg.layer_types == PERIOD and cfg.conv_dim == 8448
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 0.0078125, 16.0)
    body = cfg.serving_body()
    assert body.kv_layout() == {"n_layers": 1, "n_heads": 8, "head_dim": 128,
                                "dtype": "bfloat16"}
    assert body.state_leaves(128) == {"conv": (9, (3 * 8448,), "bfloat16"),
                                      "ssd": (9, (128, 64, 128), "float32")}
    assert body.q_scale == pytest.approx(128 ** -0.5)
    shapes = jax.eval_shape(lambda: granite_h_init(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 4.755e9 < n < 4.760e9  # the issue's arithmetic: 4.757B parameters
    model = dataclasses.asdict(cfg)
    assert model["n_dense_layers"] == 0 and model["n_shared_experts"] == 1
    assert H.family_piece(config, "ssd_decode_state_bytes")(16, model) == (
        16 * 9 * 128 * 64 * 128 * 4 * 2)
    assert H.family_piece(config, "gqa_decode_kv_bytes")(1000, model) == 1000 * 4096
    assert H.family_piece(config, "moe_decode_bytes")(0, model) == 10 * (
        4096 * 72 * 2 + 3 * 4096 * 1536 * 2)
    got, _ = _build_model("granite_h", TINY, _params(), seed=0)
    assert got is TINY and build_llm_app(model="granite_h", model_cfg=TINY) is not None
    with pytest.raises(TypeError):
        _build_model("granite_h", object(), None, seed=0)
