"""LFM2-MoE (gated short convolutions with an attention layer of NARROW heads
among them, two leading dense MLPs and then an expert layer whose experts all
lie on this chip) through the engine against its plain reference.

The reference (``benchmark/reference/lfm2_moe.py``) is the equations over the
whole sequence in float32: a token loop for the convolution, a dense masked
softmax, a loop over the experts, no cache.  The engine serves chunks (the
convolution with the slot's tails in, a walk over the block table, tiles of
pairs), then decodes through the caches split by layer kind: the tails in the
conv layers, paged K/V of TWO key-value heads a row in the attention layers.
Every comparison holds one to the other on LOGITS, at a small size on the CPU
in float32: six layers in five runs (two dense conv layers, then attention,
conv, attention, conv with experts), 8 query heads on 4 key-value heads of 8
lanes packed two a row, all 8 experts held, 3 a token, a selection bias that
changes the choice.

``TOL``: float32 round-off of two summation orders reads about 3e-5 on logits
of size 7; each named fault of the program reads 3e-2 and more
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

from benchmark.families import lfm2_moe as family  # noqa: E402
from benchmark.reference import lfm2_moe as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import HybridConfig, HybridPool  # noqa: E402
from ray_tpu.llm.model_runner import host_batch, pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.lfm2 import (  # noqa: E402
    COUNTERS,
    FIRST_TEN,
    Lfm2MoeBody,
    Lfm2MoeConfig,
    lfm2_moe_init,
)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.gqa_attention import (  # noqa: E402
    gqa_chunk_attention,
    gqa_paged_attention,
    rotary_half,
)

TOL = 1e-3
TINY = Lfm2MoeConfig(
    vocab_size=192, d_model=64, n_layers=6,
    layer_types=("conv", "conv", "full_attention", "conv", "full_attention", "conv"),
    n_dense_layers=2, n_heads=8, n_kv_heads=4, head_dim=8, d_ff=96, d_expert=16,
    n_routed_experts=8, experts_held=8, experts_per_tok=3, init_range=0.25,
    # the experts as loud as the mixers here: a fault in the routing must show
    expert_out_gain=1.0, tap_range=1.0, dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    """The initializer's, with a selection bias that is NOT zero (a trained
    model's balances the load): it must choose and not weigh."""
    params = lfm2_moe_init(jax.random.PRNGKey(0), TINY)
    for i, run in enumerate(params["runs"]):
        if "router" in run:
            shape = run["router"]["bias"].shape
            run["router"]["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(100 + i), shape)
        for j, name in enumerate(("q_norm", "k_norm")):
            if name in run:  # a learned scale differs from lane to lane
                scale = run[name]["scale"]
                run[name]["scale"] = scale * jax.random.uniform(
                    jax.random.PRNGKey(200 + 2 * i + j), scale.shape, minval=0.5, maxval=1.5)
    return params


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


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_with_a_padded_tail_then_decodes_through_both_caches_match_the_reference(impl):
    # three chunks (the last 5 of 8 tokens), then decodes through the tails of
    # four layers and the packed paged K/V of two; "pallas": the paged kernel
    # with the 4 query heads of a packed row on its window axis and the expert
    # layer's batch kernel, interpreted
    want, got = _teacher_forced(_runner(attn_impl=impl), n_out=30 if impl == "xla" else 6,
                                fill=3.0)
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5 and 0.3 < want.std() < 4.0  # logits of order one


# One departure from the equations a case, planted HERE by overriding one
# small method of the body or one field of the configuration: the served
# programs hold no such switch.


class _HeadsAnotherWay(Lfm2MoeBody):
    """``_qkv`` with q and k through ``self.turn(x, scale, positions)``."""

    def _qkv(self, h, layer, positions):
        cfg, n = self.cfg, h.shape[0]
        a = self._norm(h, layer, "ln1")
        def heads(w, k):
            return jnp.dot(a, layer[w]["kernel"]).reshape(n, k, cfg.head_dim)

        return (self.turn(heads("q", cfg.n_heads), layer["q_norm"]["scale"], positions),
                self.turn(heads("k", cfg.n_kv_heads), layer["k_norm"]["scale"], positions),
                heads("v", cfg.n_kv_heads))

    def normed(self, x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + self.cfg.norm_eps) * scale


class _RotaryBeforeTheNorm(_HeadsAnotherWay):
    """q and k turned at the token's position and THEN normed: the norm's
    learned scale then lies on rotated lanes."""

    def turn(self, x, scale, positions):
        return self.normed(rotary_half(x, positions, self.cfg.rope_theta), scale)


class _NoNormOfTheHeads(_HeadsAnotherWay):
    """q and k rotated as they leave their projections."""

    def turn(self, x, scale, positions):
        return rotary_half(x, positions, self.cfg.rope_theta)


class _GateAfterTheConvolution(Lfm2MoeBody):
    """conv(x) * B * C and not conv(B * x) * C."""

    def _conv_in(self, h, layer):
        d = self.cfg.d_model
        p = jnp.dot(self._norm(h, layer, "ln1"), layer["conv_in"]["kernel"])
        return p[:, 2 * d:], p[:, :d] * p[:, d:2 * d]


def _taps_newest_first(params):
    """The taps read in the other direction (the program's copy alone)."""
    flip = lambda run: dict(run, conv={"kernel": run["conv"]["kernel"][:, ::-1]})  # noqa: E731
    return dict(params, runs=[flip(r) if "conv" in r else r for r in params["runs"]])


class _TheBiasWeighs(Lfm2MoeBody):
    """The weights are the chosen ``p + b``, normalised."""

    def _expert_mlp(self, h, layer, live, counts, phase, experts, index):
        cfg = self.cfg
        y32 = self._norm(h, layer, "ln2")
        p = jax.nn.sigmoid(jnp.dot(y32, layer["router"]["kernel"], precision="highest"))
        picked, chosen = jax.lax.top_k(p + layer["router"]["bias"], cfg.experts_per_tok)
        weights = picked / (picked.sum(-1, keepdims=True) + cfg.route_eps)
        mask, wmat = moe.held_pairs(chosen, weights, 0, cfg.experts_held, live)
        return h + moe.expert_layer(
            y32, mask, wmat, experts["gate"], experts["up"], experts["down"],
            first=index * cfg.experts_held, impl="xla"), counts


@pytest.mark.parametrize("broken", [
    dict(rope_theta=1e4), dict(route_eps=0.5), dict(routed_scaling=2.0),
    _RotaryBeforeTheNorm, _NoNormOfTheHeads, _GateAfterTheConvolution, _taps_newest_first,
    _TheBiasWeighs],
    ids=lambda b: next(iter(b)) if isinstance(b, dict) else b.__name__)
def test_one_broken_thing_fails(broken):
    cfg, params = TINY, _params()
    if isinstance(broken, dict):
        cfg = dataclasses.replace(TINY, **broken)
    elif isinstance(broken, type):
        class Config(Lfm2MoeConfig):
            def serving_body(self):
                return broken(self)

        cfg = Config(**dataclasses.asdict(TINY))
    else:
        params = broken(params)
    runner = HybridModelRunner(cfg, params, block_size=BLOCK)
    want, got = _teacher_forced(runner, n_out=6)
    assert np.abs(want - got).max() > 10 * TOL


def test_bfloat16_tails_fail_the_tolerance_of_a_float32_program():
    """The pool's dtype is the tails': a float32 program whose tails alone are
    bfloat16 reads well over ``TOL``, so the tails reach the logits."""
    runner = _runner()
    body = runner.body

    class Rounded(Lfm2MoeBody):
        def state_leaves(self, block_size):
            (name, (n, shape, _)), = body.state_leaves(block_size).items()
            return {name: (n, shape, "bfloat16")}

    class Config(Lfm2MoeConfig):
        def serving_body(self):
            return Rounded(self)

    want, got = _teacher_forced(
        HybridModelRunner(Config(**dataclasses.asdict(TINY)), _params(), block_size=BLOCK))
    assert np.abs(want - got).max() > 3 * TOL


# -- narrow heads through the paged path ----------------------------------------------


def _dense_softmax(q, k, v, n_ctx):
    """q: (H, e) at position ``n_ctx - 1``; k, v: (T, K, e).  (H, e)."""
    h, e = q.shape
    kv = k.shape[1]
    out = []
    for i in range(h):
        kh, vh = k[:n_ctx, i // (h // kv)], v[:n_ctx, i // (h // kv)]
        p = jax.nn.softmax(kh @ q[i] / np.sqrt(e))
        out.append(p @ vh)
    return np.stack(out)


@pytest.mark.parametrize("e,pack,impl", [(64, 2, "xla"), (64, 2, "pallas"), (8, 2, "xla"),
                                         (32, 4, "xla"), (64, 1, "xla")])
def test_packed_heads_through_the_paged_decode_equal_a_dense_softmax(e, pack, impl):
    """Rows of ``pack`` key-value heads side by side: K = 4 heads of ``e`` in
    ``4 / pack`` rows of ``pack * e`` lanes, 2 query heads a key-value head,
    three sequences of other lengths over a shuffled block table."""
    kv, h, bs, tmax = 4, 8, 8, 6
    lens = [37, 1, 16]
    ks = jax.random.split(jax.random.PRNGKey(e + pack), 3)
    k = jax.random.normal(ks[0], (3, tmax * bs, kv, e))
    v = jax.random.normal(ks[1], (3, tmax * bs, kv, e))
    q = jax.random.normal(ks[2], (3, h, e)) * 2.0
    order = np.random.default_rng(0).permutation(3 * tmax) + 1
    tables = order.reshape(3, tmax).astype(np.int32)
    shape = (3 * tmax + 1, kv // pack, bs, pack * e)
    k_pool, v_pool = jnp.full(shape, 7.0), jnp.full(shape, 7.0)
    for s in range(3):
        for b in range(tmax):
            rows = slice(b * bs, (b + 1) * bs)
            k_pool, v_pool = (
                pool.at[tables[s, b]].set(
                    x[s, rows].reshape(bs, kv // pack, pack * e).transpose(1, 0, 2))
                for pool, x in ((k_pool, k), (v_pool, v)))
    positions = jnp.asarray(lens, jnp.int32) - 1
    got = gqa_paged_attention(q, k_pool, v_pool, jnp.asarray(tables), positions, impl=impl)
    assert got.shape == (3, h, e)
    for s in range(3):
        want = _dense_softmax(np.asarray(q[s]), np.asarray(k[s]), np.asarray(v[s]), lens[s])
        np.testing.assert_allclose(got[s], want, atol=2e-5)
    # the chunk's walk over the same pool: the last 5 positions of sequence 0
    pos = jnp.arange(lens[0] - 5, lens[0], dtype=jnp.int32)
    qc = jax.random.normal(ks[2], (5, h, e))
    walked = gqa_chunk_attention(qc, k_pool, v_pool, jnp.asarray(tables[0]), pos, lens[0])
    for i in range(5):
        want = _dense_softmax(np.asarray(qc[i]), np.asarray(k[0]), np.asarray(v[0]),
                              int(pos[i]) + 1)
        np.testing.assert_allclose(walked[i], want, atol=2e-5)


def test_a_pool_of_whole_heads_walks_as_it_always_did():
    """The chunk's walk and the decode read the packing off the shapes: at
    ``pack`` 1 the lowered program is the one it was before either knew of
    packing (``tools/program_hashes.py`` holds every served program to that)."""
    q = jnp.zeros((5, 8, 16))
    pool = jnp.zeros((9, 4, 4, 16))
    table, positions = jnp.zeros((3,), jnp.int32), jnp.arange(5, dtype=jnp.int32)
    packed = jnp.zeros((9, 2, 4, 32))
    text = lambda p: jax.jit(gqa_chunk_attention).lower(  # noqa: E731
        q, p, p, table, positions, jnp.int32(5)).as_text()
    assert "9x4x4x16" in text(pool) and "9x2x4x32" in text(packed)
    # the same gather, one more reshape and another transpose where heads are packed
    assert text(pool).count("stablehlo.transpose") == text(packed).count("stablehlo.transpose")
    assert text(pool).count("stablehlo.gather") == text(packed).count("stablehlo.gather")


# -- the router and the expert layer ------------------------------------------------------


def test_the_router_at_the_published_epsilon_by_hand_and_the_default_bit_equal_to_todays():
    x = jnp.eye(4, dtype=jnp.float32)[:1]
    kernel = jnp.zeros((4, 6)).at[0].set(jnp.array([0.0, 4.0, -3.0, 2.0, -1.0, -5.0]))
    # the bias lifts expert 4 over expert 0 and weighs nothing
    bias = jnp.array([0.0, 0.0, 0.0, 0.0, 0.3, 0.0])
    chosen, weights = moe.route(x, kernel, bias, 3, 1.0, eps=1e-6)
    assert chosen.tolist() == [[1, 3, 4]]
    p = 1.0 / (1.0 + np.exp(-np.array([4.0, 2.0, -1.0])))
    np.testing.assert_allclose(weights[0], p / (p.sum() + 1e-6), rtol=1e-6)
    # a LARGE epsilon shows where it stands: in the sum, not on each score
    _, wide = moe.route(x, kernel, bias, 3, 2.0, eps=0.5)
    np.testing.assert_allclose(wide[0], 2.0 * p / (p.sum() + 0.5), rtol=1e-6)
    # the default is Kimi-K2.5's program, bit for bit and op for op
    lay = jax.random.normal(jax.random.PRNGKey(1), (21, 16))
    router = jax.random.normal(jax.random.PRNGKey(2), (16, 12)) * 0.25
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (12,))

    def todays(x32, router_kernel, select_bias, top_k, scaling):
        z = jnp.dot(x32, router_kernel.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        p = jax.nn.sigmoid(z)
        _, chosen = jax.lax.top_k(p + select_bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(p, chosen, axis=-1)
        return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling

    for got, want in zip(moe.route(lay, router, b, 4, 2.827), todays(lay, router, b, 4, 2.827)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    texts = [jax.jit(lambda *a, f=f: f(*a, 4, 2.827)).lower(lay, router, b).as_text()
             for f in (moe.route, todays)]
    assert texts[0].split("\n", 1)[1] == texts[1].split("\n", 1)[1]


def _layer(seed=5, n=21, d=16, f=8, experts=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (n, d)),
        router=jax.random.normal(ks[1], (d, experts)) * d**-0.5,
        bias=0.2 * jax.random.normal(ks[5], (experts,)),
        gate=jax.random.normal(ks[2], (experts, d, f)) * d**-0.5,
        up=jax.random.normal(ks[3], (experts, d, f)) * d**-0.5,
        down=jax.random.normal(ks[4], (experts, f, d)) * f**-0.5)


def _share(lay, offset, held, tile=64):
    """What one chip holding ``held`` experts from ``offset`` adds."""
    chosen, weights = moe.route(lay["x"], lay["router"], lay["bias"], 4, 1.0, eps=1e-6)
    mask, wmat = moe.held_pairs(chosen, weights, offset, held, jnp.ones(lay["x"].shape[0], bool))
    cut = lambda k: lay[k][offset:offset + held]  # noqa: E731
    return moe.expert_layer(lay["x"], mask, wmat, cut("gate"), cut("up"), cut("down"),
                            top_k=4, tile=tile)


@pytest.mark.parametrize("shares,tile", [(1, 64), (2, 64), (4, 64), (2, 5), (4, 3)])
def test_the_shares_add_up_to_the_uncut_reference_layer(shares, tile):
    """Guide section 4: the parts that ``shares`` chips give (offsets 0, 16 /
    shares, ...; at the published deployment ONE chip holds all) add up to the
    plain reference's whole expert layer, its residual taken off."""
    lay = _layer()
    held = 16 // shares
    consts = reference._frozen(dict(family.reference_sizes(TINY), experts_per_tok=4))
    w = {"ln2": {"scale": jnp.ones(16)}, "router": {"kernel": lay["router"], "bias": lay["bias"]}}
    # the reference norms its input: give both the normed rows
    x = lay["x"] / jnp.sqrt((lay["x"] ** 2).mean(-1, keepdims=True) + TINY.norm_eps)
    lay["x"] = x
    total = sum(_share(lay, s * held, held, tile=tile) for s in range(shares))
    with jax.default_matmul_precision("highest"):
        whole, mask, _ = reference._experts(
            x, w, {k: lay[k] for k in ("gate", "up", "down")}, consts)
    assert int(mask.sum()) == 21 * 4
    assert np.abs(np.asarray(total) - (np.asarray(whole) - np.asarray(x))).max() < 1e-5


@pytest.mark.parametrize("fault", ["none", "next_expert", "coarse_gate"])
def test_the_expert_layer_probe_holds_both_forms_to_the_reference_layer_by_layer(fault):
    """``families/lfm2_moe.py::expert_layer_deviation``, what the cell's
    reference step runs on the chip: the program's expert layer as a chunk (the
    grouped form) and as a decode batch (the batch form) on the stream the
    reference has at each expert layer.  The programs pass; every pair through
    the NEXT expert's weights, or gate matrices 3% off, fail in every layer
    and both forms."""
    tokens = _prompt(5, 120)
    rows, chunk = family.probe_rows(99, len(tokens) - 1)  # a prompt of 100, 21 tokens out
    assert chunk == 100 and list(rows[-family.PROBE_BATCH:]) == list(range(104, 120))
    taps = {"rows": rows}
    reference.forward(_params(), tokens, family.reference_sizes(TINY), taps)
    assert len(taps["layers"]) == TINY.n_expert_layers
    experts = _params()["experts"]
    planted = {
        "none": experts,
        "next_expert": {k: jnp.roll(v, 1, axis=0) for k, v in experts.items()},
        "coarse_gate": dict(experts, gate=experts["gate"] * (1 + 0.03 * jnp.sign(experts["up"]))),
    }[fault]
    layers = family.expert_layer_deviation(
        TINY, dict(_params(), experts=planted), taps, chunk)
    each = [x[form] for x in layers for form in ("chunk", "decode")]
    assert len(each) == 2 * TINY.n_expert_layers
    assert all(x["chunk_rows"] > 90 and x["decode_rows"] > 12 for x in layers)
    if fault == "none":
        assert max(each) < 1e-5 < family.EXPERT_LAYER_TOLERANCE
    else:
        assert min(each) > family.EXPERT_LAYER_TOLERANCE


def test_the_reference_leaves_open_the_rows_within_the_margin_and_no_others():
    seq, rows = _prompt(9, 40), list(range(40))
    sizes = family.reference_sizes(TINY)
    _, _, margins = reference.forward(_params(), seq, sizes)
    margins = np.asarray(margins)
    assert margins.shape == (40,) and (margins > 0).all() and np.isfinite(margins).all()
    cut = float(np.sort(margins)[10] + np.sort(margins)[11]) / 2
    logits = np.asarray(reference.logits_at(_params(), seq, rows, sizes, cut))
    silent = (logits == 0).all(axis=-1)
    assert silent.sum() == 11 and (silent == (margins < cut)).all()
    # every row speaks for a float32 program: the family passes no margin
    assert not (_reference(seq, rows) == 0).all(axis=-1).any()


# -- the runs, the pools and the counters ---------------------------------------------


def test_pools_and_tails_are_updated_in_place():
    """No pool-sized temporary in either step, through five layer loops."""
    runner = _runner()
    rows, i32 = 4, np.int32
    pool = _pool(runner, slots=64)
    pools = sum(a.nbytes for a in pool.arrays)
    z = np.zeros(rows)
    ops = host_batch(z.astype(i32), z.astype(i32), np.zeros((rows, 1 + TABLE), i32),
                     z, z, np.ones(rows), z, z)
    for lowered, bound in (
            (runner._decode.lower(runner.params, *pool.arrays, *runner._counts, *ops), 0.25),
            (runner._prefill.lower(
                runner.params, *pool.arrays, *runner._counts, np.zeros(CHUNK, i32), i32(0),
                i32(CHUNK), np.zeros(1 + TABLE, i32), GREEDY, chunk=CHUNK), 0.25)):
        mem = lowered.compile().memory_analysis()
        assert mem.temp_size_in_bytes < bound * pools, (mem.temp_size_in_bytes, pools)
        assert mem.alias_size_in_bytes >= pools


@pytest.mark.parametrize("n_layers,dense,runs", [
    (10, 2, (("conv", "dense", 2), ("full_attention", "moe", 1), ("conv", "moe", 3),
             ("full_attention", "moe", 1), ("conv", "moe", 3))),
    (10, 3, (("conv", "dense", 2), ("full_attention", "dense", 1), ("conv", "moe", 3),
             ("full_attention", "moe", 1), ("conv", "moe", 3))),
    (10, 1, (("conv", "dense", 1), ("conv", "moe", 1), ("full_attention", "moe", 1),
             ("conv", "moe", 3), ("full_attention", "moe", 1), ("conv", "moe", 3))),
    (7, 0, (("conv", "moe", 2), ("full_attention", "moe", 1), ("conv", "moe", 3),
            ("full_attention", "moe", 1))),
])
def test_the_runs_are_cut_by_mixer_and_feed_forward_and_the_ledger_by_layer_kind(
        n_layers, dense, runs):
    cfg = dataclasses.replace(TINY, n_layers=n_layers, layer_types=FIRST_TEN[:n_layers],
                              n_dense_layers=dense)
    assert cfg.runs() == runs
    body = cfg.serving_body()
    kv_layers, conv_layers = cfg.n_of("full_attention"), cfg.n_of("conv")
    pool = HybridPool(HybridConfig(41, BLOCK, TABLE, 2), body.kv_layout(),
                      body.state_leaves(BLOCK))
    k, v, tails = pool.arrays
    # TWO key-value heads of 8 lanes a row: 2 rows of 16 a token a layer
    assert k.shape == v.shape == (kv_layers, 41, 2, BLOCK, 16)
    assert tails.shape == (conv_layers, 3, 2, 64)
    assert pool.block_bytes == kv_layers * 2 * (4 * BLOCK * 8 * 4)  # 4 heads of 8: unpadded
    assert pool.states.leaf_bytes() == {"tails": tails.nbytes}
    shapes = jax.eval_shape(lambda: lfm2_moe_init(jax.random.PRNGKey(0), cfg))
    assert [r["ln1"]["scale"].shape[0] for r in shapes["runs"]] == [n for _, _, n in runs]
    assert [("mlp" in r, "router" in r) for r in shapes["runs"]] == [
        (ff == "dense", ff == "moe") for _, ff, _ in runs]
    # the flat expert arrays start at the first EXPERT layer
    assert shapes["experts"]["gate"].shape == ((n_layers - dense) * 8, 64, 16)
    assert body.counters()[0].shape == (1, len(COUNTERS) + 8)


@pytest.mark.parametrize("own", [1.0, 0.1])
def test_a_layers_experts_are_akin_as_far_as_the_initializer_is_told(own):
    """``expert_own_share``: the experts of ONE layer are ``sqrt(1 - own^2)``
    of a matrix the layer draws and ``own`` of their own, in all three
    matrices and at the same spread; experts of two layers share nothing; at 1
    none do."""
    cfg = dataclasses.replace(TINY, expert_own_share=own, expert_out_gain=3.0)
    experts = lfm2_moe_init(jax.random.PRNGKey(3), cfg)["experts"]
    held, corr = cfg.experts_held, lambda a, b: float(np.corrcoef(a.ravel(), b.ravel())[0, 1])
    for name, std in (("gate", 64**-0.5), ("up", 64**-0.5), ("down", 3.0 * 16**-0.5)):
        w = np.asarray(experts[name])
        assert w.shape[0] == cfg.n_expert_layers * held
        assert w.std() == pytest.approx(std, rel=0.05)
        assert corr(w[0], w[1]) == pytest.approx(1 - own * own, abs=0.08)
        assert corr(w[held], w[2 * held - 1]) == pytest.approx(1 - own * own, abs=0.08)
        assert abs(corr(w[0], w[held])) < 0.08


def test_a_layer_pattern_must_name_every_layer_and_both_kinds():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, n_layers=5)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=("conv",) * 6)
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(TINY, expert_offset=2)
    with pytest.raises(ValueError, match="an expert layer follows"):
        dataclasses.replace(TINY, n_dense_layers=6)
    with pytest.raises(ValueError, match="whole groups"):
        dataclasses.replace(TINY, n_kv_heads=1, n_heads=8)


def test_a_share_of_the_layer_is_one_configuration_away():
    """Experts 4-7 of 8 held: the programs add their part alone and the
    reference, given the same share, agrees."""
    cfg = dataclasses.replace(TINY, experts_held=4, expert_offset=4, expert_parallel=2)
    params = lfm2_moe_init(jax.random.PRNGKey(0), cfg)
    assert params["experts"]["gate"].shape[0] == 4 * 4
    runner = HybridModelRunner(cfg, params, block_size=BLOCK)
    seq = _prompt(11, 19)
    pool = _pool(runner)
    pool.allocate("seq", len(seq))
    table, got, rows = pool.table_row("seq"), [], []
    for pos in range(0, len(seq), CHUNK):
        piece = seq[pos:pos + CHUNK]
        buf = np.zeros(CHUNK, np.int32)
        buf[:len(piece)] = piece
        *arrays, logits, _, _ = runner.prefill_chunk(
            *pool.arrays, buf, pos, len(piece), table, GREEDY)
        pool.arrays = arrays
        rows.append(pos + len(piece) - 1)
        got.append(np.asarray(logits))
    want = np.asarray(family.reference_logits(params, seq, rows, cfg))
    assert np.abs(want - np.stack(got)).max() < TOL
    assert 0 < runner.counters()()["moe"]["chunk_pairs"] < 4 * 19 * 3


# -- the served path ---------------------------------------------------------------------


def _drive(eng, reqs):
    while not all(r.finished for r in reqs):
        eng.step()
    return [list(r.out) for r in reqs]


def test_the_served_path_preempted_and_resumed_matches_the_reference():
    """``LLMEngine`` itself, several requests side by side over several
    chunks each.  Few blocks: sequences growing past them are preempted
    (recompute: the next first chunk overwrites a slot's tails) and must give
    the tokens of an engine that never preempts; every token served lies
    within ``TOL`` of the reference's largest logit at its position."""
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


@pytest.mark.parametrize("chunk", [CHUNK, 72], ids=["batch_form_chunks", "grouped_form_chunk"])
def test_stats_moe_and_both_pools_count_what_a_hand_count_gives(chunk):
    eng = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, prefill_chunk=chunk)))
    prompt, n_out = _prompt(40, 19), 9
    out = eng.generate(prompt, SamplingParams(max_tokens=n_out))
    got = eng.stats()
    moe_n, kv_n, state_n = got["moe"], got["kv_pool"], got["state_pool"]
    # by hand: the reference's own choice at every token the programs were fed
    seq = prompt + out[:-1]
    _, held, _ = reference.forward(_params(), seq, family.reference_sizes(TINY))
    held = np.stack([np.asarray(m) for m in held])              # (expert layers, tokens, held)
    assert held.shape == (4, len(seq), 8)
    by_chunks, by_decodes = held[:, :len(prompt)], held[:, len(prompt):]
    assert moe_n["chunks"] == -(-len(prompt) // chunk) and chunk % BLOCK == 0
    assert moe_n["decodes"] == kv_n["decodes"] == n_out - 1
    assert moe_n["chunk_pairs"] == by_chunks.sum() == 4 * 19 * 3
    assert moe_n["decode_pairs"] == by_decodes.sum()
    # a chunk's touched experts, computed rows and steps, chunk by chunk and
    # layer by layer
    pieces = [by_chunks[:, a:a + chunk] for a in range(0, len(prompt), chunk)]
    assert moe_n["chunk_touched"] == sum(int(p.any(axis=1).sum()) for p in pieces)
    if chunk <= moe.TILE:
        # 8 rows are no more than a tile: the batch form, where a touched
        # expert sees the chunk's 8 rows and the grouped form makes no step
        assert moe_n["chunk_tile_rows"] == moe_n["chunk_touched"] * chunk
        assert moe_n["chunk_expert_steps"] == 0
    else:
        # 72 rows, 19 of them the prompt's: the grouped form, ONE step a
        # touched expert, its pairs in blocks of the 80 rows that hold 72
        blocks = sum(int(np.ceil(p.sum(axis=1) / 80).sum()) for p in pieces)
        assert moe.row_block(chunk) == 80 and blocks == moe_n["chunk_touched"]
        assert moe_n["chunk_tile_rows"] == blocks * 80
        assert moe_n["chunk_expert_steps"] == moe_n["chunk_touched"] > 0
    # a decode of ONE live row touches as many experts as it has pairs, and
    # each touched expert sees the batch's rows, all through the batch form
    assert moe_n["decode_touched"] == moe_n["decode_pairs"]
    assert moe_n["decode_tile_rows"] == moe_n["decode_touched"] * SLOTS
    assert moe_n["decode_expert_steps"] == moe_n["decode_touched"] > 0
    assert moe_n["load"] == [int(x) for x in held.sum(axis=(0, 1))]
    assert set(moe_n) == set(COUNTERS) | {"load"}
    # the decodes' occupancy: the same three counts under both pools
    for pool_n in (kv_n, state_n):
        assert pool_n["decodes"] == pool_n["decode_rows"] == n_out - 1
        assert pool_n["decode_tokens"] == sum(range(len(prompt) + 1, len(prompt) + n_out))
    assert kv_n["block_tokens"] == BLOCK and kv_n["blocks"] == SLOTS * TABLE
    assert kv_n["bytes"] == eng.pool.kv.device_bytes  # TWO layers' K and V, unpadded
    assert kv_n["bytes"] == 2 * 2 * (SLOTS * TABLE + 1) * 4 * BLOCK * 8 * 4
    assert set(state_n["kinds"]) == {"tails"} and state_n["slots"] == SLOTS
    assert state_n["chunks"] == moe_n["chunks"] and state_n["chunk_tokens"] == len(prompt)


@pytest.mark.parametrize("knob,why", [
    (dict(prefix_cache=True), "Lfm2MoeConfig: the radix prefix cache shares blocks"),
    (dict(prefix_cache=True), "a recurrent state beside 2 layers' keys and values"),
    (dict(prefix_cache=False, spec_k=2), "Lfm2MoeConfig: verifying k drafted tokens"),
    (dict(prefix_cache=False, tp=2), "Lfm2MoeConfig: tensor parallelism"),
])
def test_the_engine_refuses_what_a_state_cannot_do_and_names_the_family(knob, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **knob)))


def test_the_family_is_found_by_name_at_the_published_widths():
    from benchmark import harness as H
    from ray_tpu.serve.llm import _FAMILIES, _build_model

    config = H.load_config(H.manifest(), "lfm2-24b-a2b-l10-1chip")
    assert sorted(config["reduced"]) == ["num_hidden_layers"]
    assert config["correctness"]["routing_margin"] == reference.ROUTING_MARGIN
    cfg = H.family_piece(config, "model_config")(H.sizes(config, False))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size,
            cfg.n_layers, cfg.n_dense_layers, cfg.conv_taps, cfg.d_ff, cfg.d_expert,
            cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset, cfg.experts_per_tok,
            cfg.expert_parallel) == (
                2048, 32, 8, 64, 65536, 10, 2, 3, 11776, 1536, 64, 64, 0, 4, 1)
    # the experts as loud as a mixer and akin (the file's ``assumed.expert_init``)
    assert (cfg.expert_out_gain, cfg.expert_own_share) == (3.0, 0.1)
    assert config["correctness"]["expert_layer_tolerance"] == family.EXPERT_LAYER_TOLERANCE
    assert cfg.layer_types == FIRST_TEN and cfg.n_of("conv") == 8
    assert (cfg.route_eps, cfg.routed_scaling, cfg.rope_theta, cfg.norm_eps) == (
        1e-6, 1.0, 1e6, 1e-5)
    body = cfg.serving_body()
    # 8 heads of 64 as 4 rows of 128 lanes: 1,024 B of K a token a layer
    assert body.kv_layout() == {"n_layers": 2, "n_heads": 4, "head_dim": 128,
                                "dtype": "bfloat16"}
    assert body.state_leaves(128) == {"tails": (8, (2, 2048), "bfloat16")}
    shapes = jax.eval_shape(lambda: lfm2_moe_init(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 5.265e9 < n < 5.270e9  # the issue's arithmetic: about 5,267M parameters
    assert shapes["experts"]["gate"].shape == (8 * 64, 2048, 1536)
    model = dataclasses.asdict(cfg)
    assert model["n_layers"] - model["n_dense_layers"] == 8
    # the family's counts against the issue's arithmetic
    assert family.moe_decode_bytes(0, model) == 8 * (2048 * 64 * 2 + 64 * 4)
    assert family.moe_decode_bytes(41, model) - family.moe_decode_bytes(40, model) == 18874368
    assert family.moe_chunk_bytes(512, model) == family.moe_decode_bytes(512, model)
    assert family.moe_pair_flops(model) == 6 * 2048 * 1536
    assert family.gqa_decode_kv_bytes(1, model) == 2 * 2 * 8 * 64 * 2     # 4,096 B a token
    assert family.short_conv_decode_bytes(0, model) == 8 * 16783360 * 2
    assert (family.short_conv_decode_bytes(16, model) - family.short_conv_decode_bytes(0, model)
            == 8 * 16 * 2 * 2048 * 2 * 2)
    # and by name on the normal path
    assert _FAMILIES[family.SERVE_MODEL][1] == "Lfm2MoeConfig"
    served, _ = _build_model("lfm2_moe", TINY, _params(), 0)
    assert served is TINY
    rehearsal = H.family_piece(config, "model_config")(H.sizes(config, True))
    assert rehearsal.runs()[0] == ("conv", "dense", 2) and rehearsal.dtype == "float32"
