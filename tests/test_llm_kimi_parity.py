"""Kimi-K2.5's block (latent attention over a paged latent pool, a dropless
expert layer over the experts one chip holds) through the engine against its
plain reference.

The reference (``benchmark/reference/kimi_k2.py``) is the equations over the
whole sequence in float32: expanded attention, a loop over the held experts,
no cache.  The engine serves chunks, block tables, the absorbed form in a
decode, tiles of (token, expert) pairs, and shares and forks latent blocks
through the radix prefix cache.  Every comparison holds one to the other at
a small size on the CPU in float32: 1 dense + 3 expert layers, 4 of 16
experts held, 4 a token.

``TOL``: float32 round-off of two summation orders reads about 1e-6 on
logits of size 1; a fault of the program reads 1e-2 and more.
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

from benchmark.families import kimi_k2 as family  # noqa: E402
from benchmark.reference import kimi_k2 as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import CacheConfig, KVBlockPool  # noqa: E402
from ray_tpu.llm.model_runner import pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.kimi_k2 import (  # noqa: E402
    KimiK2Config,
    kimi_k2_init,
    softmax_scale,
    yarn_inv_freq,
)
from ray_tpu.ops import latent_attention as la  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.moe import COUNTERS  # noqa: E402

TOL = 1e-4
TINY = KimiK2Config(
    vocab_size=160, seq_len=4096, d_model=32, n_layers=4, n_dense_layers=1, n_heads=4,
    q_lora_rank=16, kv_lora_rank=128, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    d_ff=64, d_expert=16, n_routed_experts=16, experts_held=4, expert_offset=4,
    expert_parallel=4, experts_per_tok=4, rope_original_max_position=16, rope_factor=4.0,
    init_range=0.5, dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    return kimi_k2_init(jax.random.PRNGKey(0), TINY)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, TINY.vocab_size, n)]


def _reference(tokens, rows, cfg=TINY):
    return np.asarray(reference.logits_at(
        _params(), tokens, rows, **family.reference_sizes(cfg)))


@functools.lru_cache(maxsize=None)
def _runner(**over):
    return HybridModelRunner(dataclasses.replace(TINY, **over), _params(), block_size=BLOCK)


def _pool(runner, fill=0.0):
    pool = KVBlockPool(CacheConfig(SLOTS * TABLE + 1, BLOCK, TABLE), **runner.body.kv_layout())
    if fill:  # a pool that starts as noise: nothing may be read before it is written
        pool.arrays = tuple(jnp.full(a.shape, fill, a.dtype) for a in pool.arrays)
    return pool


def _teacher_forced(runner, n_prompt=21, n_out=14, fill=0.0):
    """Prefill ``n_prompt`` tokens in chunks, then decode the sequence's own
    next tokens one step at a time in batch row 1, beside two dead rows.
    Returns (reference logits, engine logits) at the chunks' last tokens and
    at every decode position."""
    seq = _prompt(2, n_prompt + n_out)
    pool = _pool(runner, fill=fill)
    pool.allocate("other", 5)  # so the sequence's blocks are not the first ones
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
def test_chunks_then_decodes_through_the_latent_pool_match_the_reference(impl):
    # three chunks (the last ragged: it ends inside a block), then 14 decodes
    # that cross three block boundaries, from a pool that starts as noise
    want, got = _teacher_forced(_runner(attn_impl=impl), fill=3.0)
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5  # the logits are not all alike


def _engine(**over):
    return LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, prefix_cache=False, **over)))


@pytest.fixture(scope="module")
def engine():
    """ONE roomy engine without a prefix cache for the cases that only serve
    through it, its programs compiled once a module.  Its counters only grow:
    a case reads what ITS requests added."""
    return _engine()


def _deficits(prompt, out):
    """The reference's largest logit less its logit of the engine's token,
    at every output position (the benchmark's own statistic)."""
    rows = list(range(len(prompt) - 1, len(prompt) - 1 + len(out)))
    logits = _reference(prompt + out[:-1], rows)
    return logits.max(-1) - logits[np.arange(len(out)), np.asarray(out)]


def test_a_preempted_sequence_is_recomputed_to_the_same_tokens(engine):
    prompts = [_prompt(10 + i, 17 + 3 * i) for i in range(3)]
    roomy = engine
    want = [roomy.generate(p, SamplingParams(max_tokens=20)) for p in prompts]
    # 3 sequences of up to 43 tokens (11 blocks each) in 22 blocks: the youngest goes
    tight = _engine(num_blocks=23)
    reqs = [tight.submit(p, SamplingParams(max_tokens=20)) for p in prompts]
    while tight.has_work():
        tight.step()
    assert tight.stats()["preemptions"] > 0
    assert [r.out for r in reqs] == want
    for p, out in zip(prompts, want):
        assert _deficits(p, out).max() < TOL
    assert tight.pool.audit()["ok"]


# -- the radix prefix cache on latent blocks -------------------------------------------


def test_a_prefix_hit_and_a_forked_partial_block_give_the_cold_tokens(engine):
    cold = engine
    head = _prompt(30, 22)
    a, b = head + _prompt(31, 5), head[:18] + _prompt(32, 9)   # b parts INSIDE a block
    want = [cold.generate(p, SamplingParams(max_tokens=10)) for p in (a, a, b)]
    warm = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, prefix_cache=True)))
    got = [warm.generate(p, SamplingParams(max_tokens=10)) for p in (a, a, b)]
    assert got == want and want[0] == want[1]
    stats = warm.stats()["prefix_cache"]
    assert stats["hit_tokens"] >= 24 + 16 and stats["cow_forks"] >= 1
    assert warm.pool.audit()["ok"] and warm.prefix_cache.audit()["ok"]
    assert _deficits(b, got[2]).max() < TOL


@pytest.mark.parametrize("field,value,why", [
    ("tp", 2, "no head axis to shard"),
    ("spec_k", 2, "no verify program"),
])
def test_the_engine_refuses_what_the_body_cannot_do_and_says_why(field, value, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **{field: value})))


# -- the two forms of the attention ------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_absorbed_equals_expanded(impl):
    rank, rope, heads, dn, dv, bs, tmax = 128, 8, 4, 8, 8, 4, 6
    width = la.padded_width(rank, rope)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    pool = jax.random.normal(ks[0], (40, bs, width)).at[:, :, rank + rope:].set(0.0)
    w_k = jax.random.normal(ks[1], (rank, heads, dn)) * rank**-0.5
    w_v = jax.random.normal(ks[2], (rank, heads, dv)) * rank**-0.5
    lengths = np.array([23, 1, 9], np.int32)
    q_nope = jax.random.normal(ks[3], (3, heads, dn))
    q_rope = jax.random.normal(ks[4], (3, heads, rope))
    tables = np.asarray(jax.random.permutation(ks[5], 39)[:3 * tmax] + 1).reshape(3, tmax)
    q_lat = jnp.einsum("nhd,rhd->nhr", q_nope, w_k)
    q_abs = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((3, heads, width - rank - rope))], axis=-1)
    o_lat = la.latent_decode_attention(
        q_abs, pool, tables, lengths - 1, rank=rank, scale=0.3, impl=impl)
    absorbed = jnp.einsum("nhr,rhd->nhd", o_lat, w_v)
    for row in range(3):
        expanded = la.latent_chunk_attention(
            q_nope[row:row + 1], q_rope[row:row + 1], pool, tables[row],
            lengths[row:row + 1] - 1, w_k, w_v, rank=rank, scale=0.3, head_group=2, impl=impl)
        assert np.abs(np.asarray(absorbed[row]) - np.asarray(expanded[0])).max() < 1e-5


def _chunk_operands(table_blocks, heads, bs=4, dv=16, rows=8, seed=4):
    """(pool, table, q_nope, q_rope, w_k, w_v) of a tiny latent chunk."""
    rank, rope, dn = 128, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(ks[0], (table_blocks + 21, bs, la.padded_width(rank, rope)))
    table = np.asarray(jax.random.permutation(ks[1], table_blocks + 20)[:table_blocks] + 1)
    return (pool, table, jax.random.normal(ks[2], (rows, heads, dn)),
            jax.random.normal(ks[3], (rows, heads, rope)),
            jax.random.normal(ks[4], (rank, heads, dn)) * rank**-0.5,
            jax.random.normal(ks[5], (rank, heads, dv)) * rank**-0.5)


def _chunk(impl, pool, table, q_nope, q_rope, w_k, w_v, start):
    return la.latent_chunk_attention(
        q_nope, q_rope, pool, table, start + jnp.arange(q_nope.shape[0], dtype=jnp.int32),
        w_k, w_v, rank=128, scale=0.3, impl=impl)


@pytest.mark.parametrize("start,table_blocks,heads,bs,dv", [
    (0, 6, 4, 4, 16), (9, 6, 4, 4, 16), (1030, 300, 4, 4, 16),
    # 1,200 keys: two key tiles of 600, each walked in two sub-tiles of 300
    (600, 300, 4, 4, 16),     # the chunk starts exactly on a key tile's edge
    (596, 300, 4, 4, 16),     # its diagonal crosses TWO tiles
    (1192, 300, 4, 4, 16),    # a tile every query sees whole, then the diagonal's
    (1500, 450, 2, 4, 16),    # tiles of 900 in THREE sub-tiles; two heads, one grid step
    (9, 6, 3, 4, 16),         # an odd head count: one head a grid step
    (596, 300, 6, 4, 16),     # six heads: two a grid step, three steps a tile
    (700, 6, 2, 128, 128),    # whole lane tiles: the statistics replicated over 128 lanes
])
def test_the_chunk_kernel_equals_the_dense_softmax(start, table_blocks, heads, bs, dv):
    """A chunk of 8 queries from ``start`` (inside a block; on, across and
    past key tiles' edges) through the flash kernel and through XLA."""
    pool, table, *rest = _chunk_operands(table_blocks, heads, bs, dv)
    assert start + 8 <= table_blocks * bs
    want = _chunk("xla", pool, table, *rest, start)
    got = _chunk("pallas", pool, table, *rest, start)
    assert got.shape == (8, heads, dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_a_row_s_bits_do_not_depend_on_the_chunk_it_comes_in():
    """The same query row at the same position over the same table and pool,
    inside two chunks of different ``start``: keys 0-599 are a tile every
    query of the chunk from 604 sees whole (the path without a mask) and the
    diagonal's tile of the chunk from 592 (the masked path), and the row's
    output is the same to the bit.  So is a real row's whatever the padded
    rows behind it hold (a chunk is padded to its fixed length)."""
    pool, table, q_nope, q_rope, w_k, w_v = _chunk_operands(300, 4, rows=28)
    assert la._key_tile(1200, interpret=True) == 600

    def rows(lo, start, pad_from=16, pad=0.0):
        """16 rows from position ``start``; row i is query ``lo + i``, from
        ``pad_from`` on something else."""
        qn, qr = q_nope[lo:lo + 16], q_rope[lo:lo + 16]
        keep = (jnp.arange(16) < pad_from)[:, None, None]
        return np.asarray(_chunk("pallas", pool, table, jnp.where(keep, qn, pad),
                                 jnp.where(keep, qr, -pad), w_k, w_v, start))

    early, late = rows(0, 592), rows(12, 604)       # positions 604-607 lie in both
    assert np.array_equal(early[12:], late[:4])
    assert np.abs(early[12:]).max() > 0
    padded = rows(12, 604, pad_from=4, pad=7.0)     # 4 real rows, 12 of padding
    assert np.array_equal(padded[:4], late[:4])
    assert not np.array_equal(padded[4:], late[4:])


# -- the expert layer -------------------------------------------------------------------------


def _layer(seed=5, n=21, d=16, f=8, experts=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (n, d)),
        router=jax.random.normal(ks[1], (d, experts)) * d**-0.5,
        gate=jax.random.normal(ks[2], (experts, d, f)) * d**-0.5,
        up=jax.random.normal(ks[3], (experts, d, f)) * d**-0.5,
        down=jax.random.normal(ks[4], (experts, f, d)) * f**-0.5,
        bias=jnp.zeros(experts))


def _share(lay, offset, held, top_k=4, tile=64, bias=None, impl="auto"):
    """The routed part one chip holding ``held`` experts from ``offset`` adds."""
    chosen, weights = moe.route(lay["x"], lay["router"], lay["bias"] if bias is None else bias,
                                top_k, 2.5)
    mask, wmat = moe.held_pairs(chosen, weights, offset, held, jnp.ones(lay["x"].shape[0], bool))
    cut = lambda k: lay[k][offset:offset + held]  # noqa: E731
    return moe.expert_layer(lay["x"], mask, wmat, cut("gate"), cut("up"), cut("down"),
                            top_k=top_k, tile=tile, impl=impl), mask


def _uncut(lay, top_k=4, bias=None):
    """Every expert on every token, the weight 0 where it was not chosen."""
    chosen, weights = moe.route(lay["x"], lay["router"], lay["bias"] if bias is None else bias,
                                top_k, 2.5)
    with jax.default_matmul_precision("highest"):
        out = jnp.zeros_like(lay["x"])
        for e in range(lay["router"].shape[1]):
            w_e = (weights * (chosen == e)).sum(-1, keepdims=True)
            out = out + w_e * moe.swiglu(lay["x"], lay["gate"][e], lay["up"][e], lay["down"][e])
    return out


@pytest.mark.parametrize("shares,tile,impl", [
    # 21 rows: no more than a tile of 64 (the batch form, plain and as the
    # kernel in interpret mode), more than one of 4 or 3 (the grouped form, alike)
    (4, 64, "xla"), (4, 64, "pallas"), (16, 64, "pallas"), (2, 4, "auto"), (16, 3, "auto"),
    (2, 4, "pallas"), (16, 3, "pallas")])
def test_the_shares_add_up_to_the_uncut_layer(shares, tile, impl):
    lay = _layer()
    held = 16 // shares
    total = sum(_share(lay, s * held, held, tile=tile, impl=impl)[0] for s in range(shares))
    assert np.abs(np.asarray(total) - np.asarray(_uncut(lay))).max() < 1e-5


def test_the_model_s_shares_and_the_shared_expert_once_equal_the_uncut_reference():
    """Guide section 4: the parts that all the shares give, with what every
    chip computes alike (the shared expert, the residual) counted ONCE, add
    up to what the uncut reference gives for the whole layer."""
    uncut = dataclasses.replace(TINY, experts_held=16, expert_offset=0, expert_parallel=1)
    full = kimi_k2_init(jax.random.PRNGKey(1), uncut)
    layer = jax.tree_util.tree_map(lambda a: a[0], full["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (13, TINY.d_model))
    sizes = family.reference_sizes(uncut)
    args = (sizes["eps"], sizes["top_k"], sizes["scaling"])
    whole = reference._expert_mlp(h, layer, *args, 0)[0]
    cut = lambda tree, o: {k: v[o:o + 4] for k, v in tree.items()}  # noqa: E731
    parts = [reference._expert_mlp(h, dict(layer, experts=cut(layer["experts"], o)), *args, o)[0]
             for o in range(0, 16, 4)]
    # each part is h + shared + its routed share: leave h + shared in once
    total = sum(parts) - 3 * (h + reference._swiglu(
        reference._rmsnorm(h, layer["ln2"]["scale"], sizes["eps"]),
        *(layer["shared"][k] for k in ("gate", "up", "down"))))
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 1e-5
    # and the program's layer, one share, against the reference's same share
    body = dataclasses.replace(TINY, expert_offset=8).serving_body()
    counts = jnp.zeros(len(COUNTERS) + 4, jnp.int32)
    got, counts = body._expert_mlp(
        h, dict(layer, experts=cut(layer["experts"], 8)), jnp.ones(13, bool), counts, "decode")
    assert np.abs(np.asarray(got) - np.asarray(parts[2])).max() < 1e-5
    assert int(counts[0]) == int(counts[len(COUNTERS):].sum()) > 0


@pytest.mark.parametrize("tile,impl", [(64, "xla"), (64, "pallas"), (4, "auto")])
def test_no_pair_is_dropped_when_every_row_chooses_one_expert(tile, impl):
    lay = _layer()
    bias = jnp.zeros(16).at[jnp.array([5, 1, 2, 3])].set(10.0)   # every row: 5 and three others
    out, mask = _share(lay, 4, 4, tile=tile, bias=bias, impl=impl)
    assert mask[:, 1].all() and int(mask.sum()) == 21               # expert 5 alone is held
    assert np.abs(np.asarray(out) - np.asarray(
        sum(_share(lay, e, 1, bias=bias)[0] for e in range(4, 8)))).max() < 1e-5
    # with experts 1-3 silenced, expert 5's pairs are all the uncut layer adds
    silent = dict(lay, down=lay["down"].at[jnp.array([1, 2, 3])].set(0.0))
    assert np.abs(np.asarray(out) - np.asarray(_uncut(silent, bias=bias))).max() < 1e-5


def test_the_selection_bias_chooses_and_does_not_weigh():
    lay = _layer()
    plain, w_plain = moe.route(lay["x"], lay["router"], lay["bias"], 4, 2.5)
    bias = jnp.zeros(16).at[7].set(5.0)
    biased, w_biased = moe.route(lay["x"], lay["router"], bias, 4, 2.5)
    assert (biased == 7).any(-1).all() and not (plain == 7).any(-1).all()
    p = jax.nn.sigmoid(jnp.dot(lay["x"], lay["router"], precision="highest"))
    picked = jnp.take_along_axis(p, biased, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * 2.5              # no bias in the weights
    assert np.abs(np.asarray(w_biased) - np.asarray(want)).max() < 1e-6
    assert np.allclose(np.asarray(w_plain.sum(-1)), 2.5, atol=1e-5)


def test_a_dead_row_has_no_pair():
    lay = _layer()
    chosen, weights = moe.route(lay["x"], lay["router"], lay["bias"], 4, 2.5)
    live = jnp.arange(21) % 2 == 0
    mask, wmat = moe.held_pairs(chosen, weights, 0, 16, live)
    assert int(mask.sum()) == 4 * 11 and not mask[1::2].any() and not wmat[1::2].any()


# -- YaRN ---------------------------------------------------------------------------------------


def test_yarn_frequencies_and_the_softmax_scale_against_the_closed_form():
    cfg = KimiK2Config()                                         # the published sizes
    i = np.arange(32)
    f = 50000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 8) / (20 - 8), 0, 1)                    # low 8, high 20
    want = f * (1 - ramp) + f / 64 * ramp
    assert np.allclose(yarn_inv_freq(cfg), want, rtol=1e-6)
    assert np.allclose(family.reference_sizes(cfg)["inv_freq"], want, rtol=1e-6)
    m = 0.1 * np.log(64) + 1
    assert abs(m - 1.41589) < 1e-5
    assert abs(softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    assert abs(softmax_scale(cfg) - 0.14468) < 1e-5
    assert abs(family.reference_sizes(cfg)["scale"] - softmax_scale(cfg)) < 1e-12


# -- where the equations leave the choice open --------------------------------------------------------


def test_the_references_margin_is_a_held_experts_distance_from_the_boundary_it_would_cross():
    """By hand, from the scores: a chosen held expert is as far from being
    dropped as the first score left out, one not chosen as far from being
    taken as the last score taken; a token's margin is the least of them."""
    layer = jax.tree_util.tree_map(lambda a: a[0], _params()["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (21, TINY.d_model))
    sizes = family.reference_sizes(TINY)
    _, held, margin = reference._expert_mlp(
        h, layer, sizes["eps"], sizes["top_k"], sizes["scaling"], sizes["offset"])
    x = reference._rmsnorm(h, layer["ln2"]["scale"], sizes["eps"])
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(x @ layer["router"]["kernel"]))
    for t in range(len(h)):
        order = np.sort(scores[t])[::-1]
        last_in, first_out = order[TINY.experts_per_tok - 1], order[TINY.experts_per_tok]
        mine = scores[t, TINY.expert_offset:TINY.expert_offset + TINY.experts_held]
        chose = mine >= last_in
        assert (np.asarray(held[t]) == chose).all()
        want = np.where(chose, mine - first_out, last_in - mine).min()
        assert want >= 0 and abs(float(margin[t]) - want) < 1e-6


@pytest.mark.parametrize("quantile", [0.0, 0.3, 0.7, 1.1])
def test_the_reference_says_nothing_on_a_row_whose_routing_is_within_the_margin(quantile):
    """``logits_at(..., margin)``: exactly the rows whose own position lies
    within the margin in some expert layer come back all zero (the harness's
    comparison reads 0 there); every other row is the row as it was."""
    seq, rows = _prompt(23, 40), list(range(8, 40))
    sizes = family.reference_sizes(TINY)
    whole = _reference(seq, rows)
    _, _, margins = reference.forward(_params(), seq, **sizes)
    least = np.stack([np.asarray(m) for m in margins]).min(axis=0)[rows]
    margin = float(np.quantile(least, min(quantile, 1.0))) * (1.0 if quantile <= 1 else 2.0)
    got = np.asarray(reference.logits_at(_params(), seq, rows, margin, **sizes))
    open_ = least < margin
    assert open_.sum() == {0.0: 0, 1.1: len(rows)}.get(quantile, open_.sum())
    assert (got[open_] == 0).all() and (got[~open_] == whole[~open_]).all()
    assert not (whole == 0).all(axis=-1).any()


@pytest.mark.parametrize("dtype,margin", [("float32", 0.0), ("bfloat16", reference.ROUTING_MARGIN)])
def test_the_family_leaves_rows_open_for_a_bfloat16_program_only(dtype, margin, monkeypatch, capsys):
    """A float32 program's choice IS determined (the rehearsal compares every
    row); the margin is what bf16 products upstream of the router move."""
    seen = {}
    monkeypatch.setattr(reference, "logits_at", lambda params, tokens, rows, margin, **sizes: (
        seen.update(margin=margin) or np.ones((len(rows), 4), np.float32)))
    family.reference_logits(_params(), [1, 2, 3], [1, 2], dataclasses.replace(TINY, dtype=dtype))
    assert seen["margin"] == margin and 0 < reference.ROUTING_MARGIN < 0.05
    assert '"undetermined": 0' in capsys.readouterr().out


# -- what the programs count on the device ------------------------------------------------------------


def test_stats_moe_counts_what_a_hand_count_gives():
    eng = _engine()
    prompt, n_out = _prompt(40, 19), 9
    out = eng.generate(prompt, SamplingParams(max_tokens=n_out))
    got = eng.stats()
    moe_n, pool_n = got["moe"], got["kv_pool"]
    # by hand: the reference's own choice at every token the programs were fed
    seq = prompt + out[:-1]
    _, held, _ = reference.forward(_params(), seq, **family.reference_sizes(TINY))
    held = np.stack([np.asarray(m) for m in held])              # (layers, tokens, held)
    fed_by_chunks, fed_by_decodes = held[:, :len(prompt)], held[:, len(prompt):]
    assert moe_n["chunks"] == 3 and moe_n["decodes"] == pool_n["decodes"] == n_out - 1
    assert moe_n["chunk_pairs"] == fed_by_chunks.sum()
    assert moe_n["decode_pairs"] == fed_by_decodes.sum()
    # a decode of ONE live row touches as many held experts as it has pairs
    assert moe_n["decode_touched"] == moe_n["decode_pairs"]
    # and each went through the batch form: the slots are no more than a tile
    assert moe_n["decode_expert_steps"] == moe_n["decode_touched"] > 0
    assert moe_n["load"] == [int(x) for x in held.sum(axis=(0, 1))]
    assert pool_n["decode_rows"] == n_out - 1
    assert pool_n["decode_tokens"] == sum(range(len(prompt) + 1, len(prompt) + n_out))
    assert pool_n["block_tokens"] == BLOCK and pool_n["blocks"] == SLOTS * TABLE


def test_chunks_alone_count_no_step_of_the_batch_form(engine):
    """``decode_expert_steps`` is the decodes': a request that ends with its
    prompt's last chunk has made none."""
    eng = engine
    before = eng.stats()["moe"]
    eng.generate(_prompt(41, 19), SamplingParams(max_tokens=1))
    moe_n = {name: n - before[name] for name, n in eng.stats()["moe"].items() if name != "load"}
    assert moe_n["chunks"] == 3 and moe_n["chunk_pairs"] > 0
    assert moe_n["decodes"] == moe_n["decode_touched"] == moe_n["decode_expert_steps"] == 0
