"""Nemotron-H (layers that are each ONE part: a Mamba-2 mixer, an attention
layer without positional encoding or an expert layer of UNGATED experts)
through the engine against its plain reference, and the ungated form of
``ops.moe`` in its four forms.

The reference (``benchmark/reference/nemotron_h.py``) is the equations over
the whole sequence in float32: a token loop for the recurrence, a dense masked
softmax, a loop over the held experts, no cache.  The engine serves chunks
(the SSD chunk form in sub-chunks, a walk over the block table, the grouped
expert form), then decodes through the caches SPLIT BY LAYER KIND: a slot of
state in the ``M`` layers, paged K/V in the ``*`` layers.  Every comparison
holds one to the other on LOGITS, at a small size on the CPU in float32: nine
layers ``MEMEM*EME`` (pairs ``(M, E) x 2, (M, -), (*, E), (M, E)``: four runs
of the three kinds the published pattern has), 8 query heads on 2 key-value
heads, 4 SSM heads in 2 groups, sub-chunks of 4 inside chunks of 8, experts
2-3 of 8 held, 3 a token, an expert width (12) stored as two lane rows of 8.

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

from benchmark.families import nemotron_h as family  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import HybridConfig, HybridPool  # noqa: E402
from ray_tpu.llm.model_runner import pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.blocks import Mamba2  # noqa: E402
from ray_tpu.models.nemotron_h import (  # noqa: E402
    PATTERN,
    NemotronHBody,
    NemotronHConfig,
    nemotron_h_init,
    pairs_of,
)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.gqa_attention import rotary_half  # noqa: E402
from ray_tpu.ops.moe import COUNTERS  # noqa: E402

TOL = 1e-3
TINY = NemotronHConfig(
    vocab_size=192, d_model=64, n_layers=9, pattern="MEMEM*EME", n_heads=8, n_kv_heads=2,
    head_dim=8, ssm_heads=4, ssm_head_dim=16, d_state=16, n_groups=2, ssm_chunk=4,
    d_expert=12, d_shared=24, n_routed_experts=8, experts_held=2, expert_offset=2,
    expert_parallel=4, experts_per_tok=3, expert_out_gain=1.0, attn_out_gain=1.0,
    expert_lanes=8, dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    return nemotron_h_init(jax.random.PRNGKey(0), TINY)


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


def _teacher_forced(runner, n_prompt=21, n_out=12, fill=0.0):
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


# -- the pattern ----------------------------------------------------------------------


def test_the_published_pattern_is_29_pairs_in_19_runs_of_three_kinds():
    cfg = NemotronHConfig()
    assert len(PATTERN) == 52 and (cfg.n_of("mamba"), cfg.n_of("attention"),
                                   cfg.n_of("moe")) == (23, 6, 23)
    assert cfg.n_dense_layers == 29  # the layers that are no expert layer
    pairs, runs = pairs_of(cfg.layer_types), cfg.runs()
    assert len(pairs) == 29 and len(runs) == 19
    assert set(r[:2] for r in runs) == {("mamba", "moe"), ("mamba", None), ("attention", "moe")}
    group = (("mamba", "moe", 2), ("mamba", None, 1), ("attention", "moe", 1))
    assert runs == group * 5 + (("mamba", "moe", 3), *group[1:], ("mamba", "moe", 4))
    # spelled back: the pairs are the pattern's letters in their order
    letters = {"mamba": "M", "attention": "*", "moe": "E", None: ""}
    assert "".join(letters[m] + letters[c] for m, c, n in runs for _ in range(n)) == PATTERN
    assert TINY.runs() == (("mamba", "moe", 2), ("mamba", None, 1), ("attention", "moe", 1),
                           ("mamba", "moe", 1))


def test_a_mixer_no_expert_layer_follows_stands_alone():
    assert pairs_of(("attention", "mamba", "moe", "mamba")) == (
        ("attention", None), ("mamba", "moe"), ("mamba", None))


@pytest.mark.parametrize("pattern", ["EM*M", "MEE*"])  # none leads it; two in a row
def test_an_expert_layer_that_follows_no_mixer_is_refused(pattern):
    with pytest.raises(ValueError, match="follows a mixer"):
        NemotronHConfig(n_layers=len(pattern), pattern=pattern)


def test_the_parameters_by_the_arithmetic_at_the_published_sizes():
    """5,875M parameters: 23 Mamba layers, 6 attention layers, 23 expert
    layers of 16 held two-matrix experts, an embedding and an untied head; and
    the zeros the experts are stored with."""
    cfg = NemotronHConfig()
    shapes = jax.eval_shape(lambda: nemotron_h_init(jax.random.PRNGKey(0), cfg))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(t))  # noqa: E731
    d = 2688
    mamba = d * 10304 + 4096 * d + 5 * 6144 + 3 * 64 + 4096 + d
    attention = 2 * d * 4096 + 2 * d * 256 + d
    moe_layer = 16 * 2 * d * 1856 + 2 * d * 3712 + d * 128 + 128 + d
    published = 23 * mamba + 6 * attention + 23 * moe_layer + 2 * 131072 * d + d
    assert published == 5_874_983_232
    # the experts' matrices are STORED at 1,920 (15 lane rows), zeros past 1,856
    assert cfg.stored(1856) == 1920 and cfg.stored(3712) == 3712
    assert count(shapes["experts"]) == 23 * 16 * 2 * d * 1920
    assert count(shapes) == published + 23 * 16 * 2 * d * 64


# -- the engine's steps against the reference ---------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_with_a_padded_tail_then_decodes_through_both_caches_match_the_reference(impl):
    # three chunks of two sub-chunks each (the last chunk 5 of 8 tokens), then
    # decodes through the slot of state of five layers and the paged K/V of
    # one; "pallas": the SSD decode kernel, the paged kernel with 4 query
    # heads a key-value head on its window axis and the batch expert kernel
    # in its ungated form, interpreted
    want, got = _teacher_forced(_runner(attn_impl=impl), n_out=14 if impl == "xla" else 4,
                                fill=3.0)
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5 and 0.3 < want.std() < 3.0  # logits of order one


class _Rotary(NemotronHBody):
    """``rope_theta`` read as used: q and k turned at the token's position."""

    def decode(self, params, x, arrays, positions, tables):
        self._positions = positions
        return super().decode(params, x, arrays, positions, tables)

    def chunk(self, params, x, arrays, start, n_valid, table):
        self._positions = start + jnp.arange(x.shape[0], dtype=jnp.int32)
        return super().chunk(params, x, arrays, start, n_valid, table)

    def _qkv(self, u, layer):
        q, k, v = super()._qkv(u, layer)
        turn = lambda a: rotary_half(a, self._positions, 10000.0).astype(a.dtype)  # noqa: E731
        return turn(q), turn(k), v


class _GatedShared(NemotronHBody):
    """The shared expert as ``silu(.)`` times itself and not ``relu(.) ** 2``."""

    def _expert_mlp(self, h, layer, live, counts, phase, experts, index):
        out, counts = super()._expert_mlp(h, layer, live, counts, phase, experts, index)
        y, sh = self._norm(h, layer, "ln2"), layer["shared"]
        return (out - moe.relu2(y, sh["up"], sh["down"])
                + moe.swiglu(y, sh["up"], sh["up"], sh["down"])), counts


class _NormOverAllGroups(NemotronHBody):
    """The gated norm over all of ``d_ssm`` and not within each of the
    groups: planted in the shared mixer (``models.blocks.Mamba2``) this body
    holds."""

    class Mixer(Mamba2):
        def out(self, y, z, layer):
            return Mamba2.out(dataclasses.replace(self, n_groups=1), y, z, layer)

    def __init__(self, cfg):
        super().__init__(cfg)
        self.ssm = self.Mixer(**{f.name: getattr(self.ssm, f.name)
                                 for f in dataclasses.fields(Mamba2)})


def _with_body(body):
    """``TINY`` served by ``body``."""
    class Config(NemotronHConfig):
        def serving_body(self):
            return body(self)

    return Config(**{f.name: getattr(TINY, f.name) for f in dataclasses.fields(TINY)})


@pytest.mark.parametrize("broken", [
    dict(routed_scaling=1.0), dict(norm_eps=1e-2), dict(experts_per_tok=4),
    dict(expert_offset=4), _Rotary, _GatedShared, _NormOverAllGroups],
    ids=lambda b: b.__name__ if isinstance(b, type) else next(iter(b)))
def test_one_broken_thing_fails(broken):
    cfg = dataclasses.replace(TINY, **broken) if isinstance(broken, dict) else _with_body(broken)
    runner = HybridModelRunner(cfg, _params(), block_size=BLOCK)
    want, got = _teacher_forced(runner, n_out=3)
    assert np.abs(want - got).max() > 10 * TOL


def test_a_bfloat16_ssd_state_fails_the_tolerance():
    want, got = _teacher_forced(_runner(state_dtype="bfloat16"))
    assert np.abs(want - got).max() > TOL


def _to3(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), tree)


def _rounded(params, which):
    """``params`` with the routed experts' matrices (``experts``) or every
    shared expert's (``shared``) at 3 bits of mantissa."""
    if which == "experts":
        return dict(params, experts=_to3(params["experts"]))
    return dict(params, runs=[dict(run, shared=_to3(run["shared"])) if "shared" in run else run
                              for run in params["runs"]])


@pytest.mark.parametrize("which,heard", [(None, False), ("experts", True), ("shared", False)])
def test_the_layer_probe_hears_the_routed_experts_matrices_and_them_alone(which, heard):
    """What every run's reference step checks (``family.reference_logits``):
    the routed part of the program's expert layer, both forms, against the
    reference's loop on the reference's own stream, layer by layer.  The
    matrices at 3 bits of mantissa stand 10 times the limit off; the shared
    expert is not the probe's (the logits hear it)."""
    seq = _prompt(9, 90)
    at, chunk = family.probe_rows(69, 89)
    assert chunk == 70 > moe.TILE and len(at) == 70 + family.PROBE_BATCH  # both forms
    taps = {"rows": at}
    reference.forward(_params(), seq, family.reference_sizes(TINY), taps)
    program = _params() if which is None else _rounded(_params(), which)
    layers = family.expert_layer_deviation(TINY, program, taps, chunk)
    assert len(layers) == TINY.n_of("moe") and all(x["chunk_rows"] > 60 for x in layers)
    each = [x[form] for x in layers for form in ("chunk", "decode")]
    if heard:
        assert min(e for e in each if e > 0) > 3 * family.EXPERT_LAYER_TOLERANCE
    else:
        assert max(each) < 1e-4 < family.EXPERT_LAYER_TOLERANCE


class _Mantissa3Routed(NemotronHBody):
    """The routed experts' products with their matrices at 3 bits of mantissa."""

    def _routed(self, h, layer, live, counts, phase, experts, index):
        return super()._routed(h, layer, live, counts, phase, _to3(experts), index)


def test_a_run_whose_routed_experts_stand_off_ends_its_reference_check():
    from benchmark import harness as H

    with pytest.raises(H.BenchFailure, match="routed part"):
        family.reference_logits(
            _params(), _prompt(9, 90), list(range(69, 90)), _with_body(_Mantissa3Routed))


def test_the_engine_serves_it_and_counts_what_its_layers_routed():
    """The normal path: scheduler, ``HybridPool``, ``HybridModelRunner``.  A
    request's greedy tokens are the reference's (a near-tie aside), and
    ``stats()`` carries the routed layer's one schema beside both pools."""
    engine = LLMEngine(TINY, _params(), EngineConfig(**ENGINE))
    prompt = _prompt(5, 19)
    out = engine.generate(prompt, SamplingParams(max_tokens=7))
    seq = prompt + out[:-1]
    logits = _reference(seq, list(range(len(prompt) - 1, len(seq))))
    deficit = logits.max(-1) - logits[np.arange(len(out)), np.asarray(out)]
    assert deficit.max() < TOL
    stats = engine.stats()
    assert set(COUNTERS) | {"load"} == set(stats["moe"]) and len(stats["moe"]["load"]) == 2
    assert stats["moe"]["decodes"] > 0 and stats["moe"]["chunks"] == 3
    # four expert layers a step: a step's touched experts are at most 4 x 2 held
    assert 0 < stats["moe"]["decode_touched"] <= stats["moe"]["decodes"] * 8
    assert stats["moe"]["decode_expert_steps"] == stats["moe"]["decode_touched"]
    assert stats["moe"]["chunk_expert_steps"] == 0  # chunks of 8 rows: the batch form
    assert "state_pool" in stats and "kv_pool" in stats


@pytest.mark.parametrize("refused", [dict(tp=2), dict(spec_k=2), dict(prefix_cache=True)])
def test_what_a_hooks_body_cannot_do_is_refused(refused):
    with pytest.raises(ValueError):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **refused)))


# -- the ungated expert, in the four forms --------------------------------------------


def _layer(seed=5, n=21, d=16, f=12, experts=16, dtype="float32"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    lay = dict(
        x=jax.random.normal(ks[0], (n, d)),
        up=jax.random.normal(ks[1], (experts, d, f)) * d**-0.5,
        down=jax.random.normal(ks[2], (experts, f, d)) * f**-0.5)
    lay = {k: v.astype(dtype) for k, v in lay.items()}
    lay["router"] = jax.random.normal(ks[3], (d, experts)) * d**-0.5
    return lay


def _pairs(lay, offset, held, top_k=4):
    x32 = lay["x"].astype(jnp.float32)
    chosen, weights = moe.route(x32, lay["router"], jnp.zeros(lay["router"].shape[1]), top_k, 2.5)
    return moe.held_pairs(chosen, weights, offset, held, jnp.ones(x32.shape[0], bool))


def _pair_loop(lay, mask, wmat, first):
    """``relu(x W_up) ** 2 W_down`` a (row, held expert) pair, by hand."""
    x, out = np.asarray(lay["x"], np.float64), np.zeros(lay["x"].shape, np.float64)
    for r, e in zip(*np.nonzero(np.asarray(mask))):
        up, down = (np.asarray(lay[k][first + e], np.float64) for k in ("up", "down"))
        out[r] += float(wmat[r, e]) * (np.maximum(x[r] @ up, 0.0) ** 2 @ down)
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("n,f,dtype", [
    (16, 12, "float32"),     # the batch form; an f that no 8 (nor 128) divides
    (16, 232, "float32"),    # 1,856 / 8: 1.8125 lane rows of 128
    (16, 32, "bfloat16"),
    (80, 12, "float32"),     # more rows than a tile: the grouped form
    (80, 232, "float32"),
    (130, 32, "bfloat16"),   # two blocks of rows for an expert with many pairs
], ids=lambda v: str(v))
def test_the_ungated_expert_in_its_four_forms_is_the_pair_loop(n, f, dtype, impl):
    lay = _layer(n=n, f=f, dtype=dtype)
    mask, wmat = _pairs(lay, 3, 6)
    layer = jax.jit(lambda x, m, w, u, dn, first: moe.expert_layer(
        x, m, w, u, dn, first=first, top_k=4, impl=impl))
    got = layer(lay["x"], mask, wmat, lay["up"], lay["down"], 3)
    assert got.shape == lay["x"].shape and got.dtype == jnp.float32
    want = _pair_loop(lay, mask, wmat, 3)
    assert np.abs(np.asarray(got) - want).max() < (5e-2 if dtype == "bfloat16" else 2e-5)
    assert np.abs(want).max() > 0.1


def test_the_weights_choose_the_experts_form():
    lay = _layer()
    x, up, down = lay["x"], lay["up"][0], lay["down"][0]
    by_hand = np.maximum(np.asarray(x) @ np.asarray(up), 0.0) ** 2 @ np.asarray(down)
    np.testing.assert_allclose(moe.expert_mlp(x, up, down), by_hand, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(moe.expert_mlp(x, up, up, down), moe.swiglu(x, up, up, down))
    assert np.abs(np.asarray(moe.expert_mlp(x, up, up, down)) - by_hand).max() > 0.1
    # the kernels' budget counts the matrices there are: Nemotron's 2,688 x
    # 1,856 goes whole (39.9 MB of 64), and an f that no 128 divides has no cut
    assert moe.block_f(2688, 1856, 2, matrices=2) == 1856
    assert 2 * 2 * 2688 * 1856 * 2 <= moe.VMEM_BUDGET
    with pytest.raises(ValueError, match="no block"):
        moe.block_f(2688, 1856, 2, budget=32 << 20, matrices=2)
    assert moe.block_f(7168, 2048, 2) == 512 and moe.block_f(7168, 2048, 2, matrices=2) == 1024


def test_the_eight_shares_and_the_shared_expert_once_equal_the_uncut_reference_layer():
    """Guide section 4: the routed parts that all the shares give (4 shares of
    2 of 8 here, 8 of 16 of 128 as published), with what every chip computes
    alike (the shared expert, the residual) counted ONCE, add up to what the
    uncut reference gives for the whole layer; and the program's layer, one
    share, is the reference's same share."""
    layer = jax.tree_util.tree_map(lambda a: a[0], _params()["runs"][0])
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    experts = {"up": jax.random.normal(ks[0], (8, 64, 12)) * 64**-0.5,   # ALL 8 of the router's
               "down": jax.random.normal(ks[1], (8, 12, 64)) * 12**-0.5}
    h = jax.random.normal(jax.random.PRNGKey(2), (13, TINY.d_model))
    cut = lambda o: jax.tree_util.tree_map(lambda a: a[o:o + 2], experts)  # noqa: E731
    consts = lambda o: family.reference_sizes(  # noqa: E731
        dataclasses.replace(TINY, expert_offset=o))
    with jax.default_matmul_precision("highest"):
        whole = reference._experts(h, layer, experts, reference._frozen(consts(0)))[0]
        y = reference._rmsnorm(h, layer["ln2"]["scale"], TINY.norm_eps)
        shares = [reference.routed_part(y, layer["router"], cut(o), consts(o))[0]
                  for o in (0, 2, 4, 6)]
        once = h + reference.shared_part(y, layer["shared"])
    assert np.abs(np.asarray(sum(shares) + once) - np.asarray(whole)).max() < 1e-5
    assert all(np.abs(np.asarray(s)).max() > 1e-2 for s in shares)
    body = TINY.serving_body()  # experts 2-3
    counts = jnp.zeros(len(COUNTERS) + 2, jnp.int32)
    got, counts = body._expert_mlp(h, layer, jnp.ones(13, bool), counts, "decode", cut(2), 0)
    assert np.abs(np.asarray(got) - np.asarray(shares[1] + once)).max() < 1e-5
    assert int(counts[0]) == int(counts[len(COUNTERS):].sum()) > 0
