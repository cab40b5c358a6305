"""Falcon-H1 (a Mamba-2 mixer AND grouped-query attention side by side in
every block) through the engine against its plain reference.

The reference (``benchmark/reference/falcon_h1.py``) is the equations over
the whole sequence in float32: a token loop for the recurrence, a dense
masked softmax, no cache.  The engine serves chunks (the SSD chunk form in
sub-chunks, a walk over the block table with a running softmax), then decodes
through BOTH caches of every layer: paged K/V and a slot of SSD state and
convolution tail.  Every comparison holds one to the other on LOGITS, at a
small size on the CPU in float32: 3 layers, 10 query heads on 2 key-value
heads, 4 SSM heads in 2 groups, sub-chunks of 4 inside chunks of 8.

``TOL``: float32 round-off of two summation orders reads about 3e-6 on
logits of size 1; each named fault of the program reads 1e-2 and more
(``test_one_broken_thing_fails``), a bfloat16 SSD state 8e-4 after 50 tokens.
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

from benchmark.families import falcon_h1 as family  # noqa: E402
from ray_tpu.llm import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.cache import HybridConfig, HybridPool  # noqa: E402
from ray_tpu.llm.model_runner import host_batch, pack_knobs  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.llm.state_runner import HybridModelRunner  # noqa: E402
from ray_tpu.models.blocks import Mamba2  # noqa: E402
from ray_tpu.models.falcon_h1 import (  # noqa: E402
    FalconH1Body,
    FalconH1Config,
    falcon_h1_init,
)

TOL = 1e-4
TINY = FalconH1Config(vocab_size=192, d_model=64, n_layers=3, n_heads=10, n_kv_heads=2,
                      head_dim=8, d_ff=96, d_ssm=64, ssm_heads=4, d_state=16, n_groups=2,
                      ssm_chunk=4, dtype="float32", attn_impl="xla")
SLOTS, CHUNK, BLOCK, TABLE = 3, 8, 4, 32
ENGINE = dict(max_slots=SLOTS, prefill_chunk=CHUNK, block_size=BLOCK,
              max_blocks_per_seq=TABLE, num_blocks=SLOTS * TABLE + 1, prefix_cache=False)
GREEDY = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=None)
def _params():
    return falcon_h1_init(jax.random.PRNGKey(0), TINY)


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
    for i in range(n_prompt, n_prompt + n_out):
        tokens = np.array([0, seq[i], 0], np.int32)
        positions = np.array([0, i, 0], np.int32)
        pool.arrays, logits = step(runner.params, pool.arrays, tokens, positions, tables)
        rows.append(i)
        got.append(np.asarray(logits[1]))
    return _reference(seq, rows), np.stack(got)


# -- the engine's steps against the reference ---------------------------------------


def test_chunks_with_a_padded_tail_then_decodes_match_the_reference():
    # three chunks of two sub-chunks each (the last chunk 5 of 8 tokens), then
    # 30 decodes through the paged K/V and the slot of state of every layer
    want, got = _teacher_forced(_runner(), fill=3.0)
    assert np.abs(want - got).max() < TOL
    assert np.abs(want).max() > 0.5 and 0.5 < want.std() < 2.0  # logits of order one


def test_the_kernels_interpreted_serve_the_same_logits():
    """``attn_impl="pallas"``: the SSD decode kernel and the paged kernel
    with 5 query heads a key-value head on its window axis."""
    want, got = _teacher_forced(_runner(attn_impl="pallas"), n_out=6, fill=3.0)
    assert np.abs(want - got).max() < TOL


# One departure from the equations a case, planted HERE by overriding one
# small method of the body: the served programs hold no such switch.


def _with_mixer(mixer):
    """A body whose Mamba-2 mixer (``models.blocks.Mamba2``, shared with
    Granite-4.0-H) is the subclass ``mixer`` of it, at the body's own sizes."""

    class Planted(FalconH1Body):
        def __init__(self, cfg):
            super().__init__(cfg)
            self.ssm = mixer(**{f.name: getattr(self.ssm, f.name)
                                for f in dataclasses.fields(Mamba2)})

    Planted.__name__ = mixer.__name__
    return Planted


class _NormBeforeGate(Mamba2):
    """``mamba_norm_before_gate`` true: RMSNorm_grouped(y) . silu(z)."""

    def out(self, y, z, layer):
        g = y.reshape(z.shape[0], self.n_groups, -1)
        g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + self.eps)
        out = g.reshape(z.shape) * layer["ssm_norm"]["scale"] * jax.nn.silu(z)
        return jnp.dot(out, layer["ssm_out"]["kernel"])


class _OneGroup(Mamba2):
    """Every head reads group 0's B and C."""

    def conv(self, window, layer):
        x, b, c = super().conv(window, layer)
        return x, jnp.broadcast_to(b[:, :1], b.shape), jnp.broadcast_to(c[:, :1], c.shape)


class _NoRotary(FalconH1Body):
    def _qkv(self, u, layer, positions):
        return super()._qkv(u, layer, jnp.zeros_like(positions))


class _MultipliersShifted(FalconH1Body):
    """The muP vector one segment on: z gets x's multiplier, and so on."""

    def __init__(self, cfg):
        super().__init__(cfg)
        shifted = dataclasses.replace(
            cfg, ssm_multipliers=cfg.ssm_multipliers[1:] + cfg.ssm_multipliers[:1])
        self.ssm = dataclasses.replace(self.ssm, in_scale=shifted.mup_vector())


class _KeysUnscaled(FalconH1Body):
    def _qkv(self, u, layer, positions):
        q, k, v = super()._qkv(u, layer, positions)
        return q, k / self.cfg.key_multiplier * 0.5, v


class _StateNotCarried(FalconH1Body):
    """Every chunk starts from an empty state (the chunk-to-chunk carry lost)."""

    def chunk(self, params, x, arrays, start, n_valid, table):
        k, v, conv, ssd = arrays
        return super().chunk(params, x, (k, v, conv, jnp.zeros_like(ssd)), start, n_valid, table)


@pytest.mark.parametrize("broken", [
    _with_mixer(_NormBeforeGate), _with_mixer(_OneGroup), _NoRotary, _MultipliersShifted,
    _KeysUnscaled, _StateNotCarried], ids=lambda b: b.__name__)
def test_one_broken_thing_fails(broken):
    class Config(FalconH1Config):
        def serving_body(self):
            return broken(self)

    runner = HybridModelRunner(Config(**dataclasses.asdict(TINY)), _params(), block_size=BLOCK)
    want, got = _teacher_forced(runner, n_out=6)
    assert np.abs(want - got).max() > 100 * TOL


def test_a_bfloat16_ssd_state_fails_the_tolerance():
    want, got = _teacher_forced(_runner(state_dtype="bfloat16"))
    assert np.abs(want - got).max() > 5 * TOL


# -- the block's parts against hand-written cases -------------------------------------


def test_the_mup_vector_lies_over_the_five_segments_in_order():
    cfg = dataclasses.replace(TINY, ssm_multipliers=(2.0, 3.0, 5.0, 7.0, 11.0))
    m = cfg.mup_vector()
    assert cfg.ssm_segments() == (64, 64, 32, 32, 4) and m.shape == (196,)
    want = [2.0] * 64 + [3.0] * 64 + [5.0] * 32 + [7.0] * 32 + [11.0] * 4
    np.testing.assert_array_equal(m, np.asarray(want, np.float32))
    # and the body splits the projection where the segments end
    body = cfg.serving_body()
    u = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)), jnp.float32)
    layer = jax.tree_util.tree_map(lambda a: a[0], _params()["blocks"])
    z, raw, step = body.ssm.project(u * cfg.ssm_in_multiplier, layer)
    p = np.asarray((u * cfg.ssm_in_multiplier) @ layer["ssm_in"]["kernel"])
    np.testing.assert_allclose(z, p[:, :64] * 2.0, rtol=1e-5)
    np.testing.assert_allclose(raw[:, :64], p[:, 64:128] * 3.0, rtol=1e-5)
    np.testing.assert_allclose(raw[:, 64:96], p[:, 128:160] * 5.0, rtol=1e-5)
    np.testing.assert_allclose(raw[:, 96:], p[:, 160:192] * 7.0, rtol=1e-5)
    np.testing.assert_allclose(
        step, np.logaddexp(0.0, p[:, 192:] * 11.0 + np.asarray(layer["dt_bias"])), rtol=1e-5)


def test_the_gate_comes_before_a_norm_within_each_group():
    body = TINY.serving_body()
    rng = np.random.default_rng(1)
    y, z = rng.normal(size=(2, 4, 16)), rng.normal(size=(2, 64))
    scale = rng.normal(size=64)
    eye = {"ssm_norm": {"scale": jnp.asarray(scale, jnp.float32)},
           "ssm_out": {"kernel": jnp.eye(64, dtype=jnp.float32)}}
    got = body.ssm.out(jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32), eye)
    gated = y.reshape(2, 64) * (z / (1.0 + np.exp(-z)))
    want = np.empty((2, 64))
    for g in range(2):  # 2 groups of 32 channels, each normalised by its own mean square
        part = gated[:, 32 * g:32 * (g + 1)]
        want[:, 32 * g:32 * (g + 1)] = part / np.sqrt(
            (part ** 2).mean(-1, keepdims=True) + TINY.rms_norm_eps)
    np.testing.assert_allclose(got, want * scale, rtol=1e-5, atol=1e-6)


def test_every_multiplied_product_is_initialised_at_its_multipliers_inverse():
    """Scores, gates and logits of order one: each weight's spread is
    ``fan_in ** -0.5`` over the multiplier its product meets."""
    cfg = dataclasses.replace(TINY, d_model=256, d_ff=512, vocab_size=512)
    blocks = falcon_h1_init(jax.random.PRNGKey(1), cfg)["blocks"]
    std = lambda a: float(np.asarray(a, np.float64).std())  # noqa: E731
    d = cfg.d_model
    assert std(blocks["k"]["kernel"]) == pytest.approx(d**-0.5 / cfg.key_multiplier, rel=0.05)
    assert std(blocks["q"]["kernel"]) == pytest.approx(cfg.score_spread * d**-0.5, rel=0.05)
    assert std(blocks["o"]["kernel"]) == pytest.approx(
        (cfg.n_heads * cfg.head_dim)**-0.5 / cfg.attention_out_multiplier, rel=0.05)
    w_in, ends = np.asarray(blocks["ssm_in"]["kernel"]), np.cumsum(cfg.ssm_segments())
    for lo, hi, m in zip([0, *ends[:-1]], ends, cfg.ssm_multipliers):
        assert std(w_in[..., lo:hi]) == pytest.approx(
            d**-0.5 / (cfg.ssm_in_multiplier * m), rel=0.1)
    a = np.exp(np.asarray(blocks["A_log"]))
    assert a.min() >= cfg.a_min and a.max() <= cfg.a_max
    step = np.logaddexp(0.0, np.asarray(blocks["dt_bias"]))
    assert step.min() >= cfg.dt_min * 0.999 and step.max() <= cfg.dt_max * 1.001


# -- in place ---------------------------------------------------------------------------


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_pools_and_states_are_updated_in_place(step):
    """No pool-sized temporary in either step (as ``test_llm_brumby_parity``
    and ``test_llm_phi4flash_parity`` hold theirs): at pools made large
    against the model, the compiled program's temporaries stay under a part
    of them and every pool is aliased to its output."""
    runner = _runner()
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
    # prefill: this CPU backend lays the state carries out anew ONCE for the
    # products that read one slot of them, as it does Brumby's and
    # Phi-4-flash's; the chip's compiler does not (0.24 GB of temporaries
    # beside 3.9 GB of pools at the cell's sizes: the configuration's
    # ``memory``).  Pools as the scan's ``xs`` / ``ys`` would be two copies
    bound = 0.25 if step == "decode" else 0.75
    assert mem.temp_size_in_bytes < bound * pools, (mem.temp_size_in_bytes, pools)
    assert mem.alias_size_in_bytes >= pools


# -- the two-ledger pool with K/V in every layer ----------------------------------------


def test_the_ledger_with_three_kv_layers_and_two_state_leaves():
    body = _runner().body  # 2 slots, 40 usable blocks of 4 tokens
    pool = HybridPool(HybridConfig(41, BLOCK, TABLE, 2), body.kv_layout(),
                      body.state_leaves(BLOCK))
    k, v, conv, ssd = pool.arrays
    assert k.shape == v.shape == (3, 41, 2, BLOCK, 8)
    assert conv.shape == (3, 3, 3 * (64 + 2 * 2 * 16)) and ssd.shape == (3, 3, 4, 16, 16)
    # a block's bytes are its rows in EVERY layer, K and V
    assert pool.block_bytes == 3 * 2 * (2 * BLOCK * 8 * 4)
    assert pool.device_bytes == k.nbytes + v.nbytes + conv.nbytes + ssd.nbytes
    assert pool.states.leaf_bytes() == {"conv": conv.nbytes, "ssd": ssd.nbytes}
    assert pool.states.block_bytes == (conv.nbytes + ssd.nbytes) // 3
    assert len(pool.allocate("a", 100)) == 25 and pool.states.blocks_of("a")[0] in (1, 2)
    assert not pool.can_allocate(64)  # blocks short (15 free, 16 asked), a slot free
    with pytest.raises(MemoryError):
        pool.allocate("b", 64)
    assert pool.states.num_free_blocks == 1  # the failed allocate gave its slot back
    pool.allocate("b", 8)
    assert not pool.can_allocate(4)  # slots short, blocks free
    assert pool.grow_to("a", 128) and not pool.grow_to("a", 129)  # the table's width
    assert pool.grow_to("b", 32) and not pool.grow_to("b", 33)    # the pool's last block
    counts, audit = pool.ledger_counts(), pool.audit()
    assert counts["seq_owned"] == 40 and counts["slots_owned"] == 2 and counts["free"] == 0
    assert audit["ok"] and sorted(audit["owners"]) == ["a", "b"] and audit["slots"]["owned"] == 2
    # preempted for blocks: both parts go back, the slot's content stays for
    # its next owner's first chunk to overwrite
    assert pool.free("b") == 8 and pool.audit()["ok"] and pool.states.num_free_blocks == 1
    pool.states.free("a")  # a sequence with blocks and no slot
    assert not pool.audit()["ok"] and pool.audit()["unpaired"] == ["a"]
    pool.kv.free("a")
    assert pool.audit()["ok"] and pool.audit()["free"] == 40


def _drive(eng, reqs):
    while not all(r.finished for r in reqs):
        eng.step()
    return [list(r.out) for r in reqs]


def test_the_served_path_preempted_and_resumed_matches_the_reference():
    """``LLMEngine`` itself, several requests side by side over several
    chunks each.  Few blocks: sequences growing past them are preempted
    (recompute: the next first chunk overwrites a slot) and must give the
    tokens of an engine that never preempts; every token served lies within
    ``TOL`` of the reference's largest logit at its position."""
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


def test_the_engines_account_of_both_caches():
    eng = LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, num_blocks=40)))
    prompts = [_prompt(100 + i, n) for i, n in enumerate((3, 8, 19, 30))]
    reqs = [eng.submit(p, SamplingParams(max_tokens=5)) for p in prompts]
    _drive(eng, reqs)
    while eng.has_work():
        eng.step()
    audit, s = eng.pool.audit(), eng.stats()
    assert audit["ok"] and audit["owned"] == 0 and audit["free"] == 39
    assert audit["slots"]["free"] == SLOTS and not audit["unpaired"]
    state, led = s["state_pool"], s["hbm"]
    assert state["slots"] == SLOTS and state["live"] == 0
    assert set(state["kinds"]) == {"conv", "ssd"} and state["bytes"] == sum(state["kinds"].values())
    # the decodes' occupancy stands under both pools, the same three counts
    assert s["kv_pool"] == {"blocks": 39, "live": 0, "block_tokens": BLOCK,
                            "bytes": eng.pool.kv.device_bytes,
                            **{k: state[k] for k in ("decodes", "decode_rows", "decode_tokens")}}
    # the memory split: K/V blocks by the ledger, the slots of state beside them
    assert led["pool_bytes"] == eng.pool.device_bytes == state["bytes"] + s["kv_pool"]["bytes"]
    assert led["block_bytes"] == eng.pool.kv.device_bytes // 40
    assert led["free_bytes"] == 39 * led["block_bytes"] and led["seq_bytes"] == 0
    assert led["state_bytes"] == state["bytes"] and led["state_seq_bytes"] == 0
    # chunks: 1 + 1 + 3 + 4 of them, every prompt token once, and the context
    # each attended (its own last token's position + 1)
    assert state["chunks"] == 9 and state["chunk_tokens"] == 3 + 8 + 19 + 30
    assert state["chunk_context_tokens"] == 3 + 8 + (8 + 16 + 19) + (8 + 16 + 24 + 30)
    assert state["overwrites"] == 4
    assert state["decode_tokens"] > state["decode_rows"] > state["decodes"] > 0


@pytest.mark.parametrize("knob,why", [
    (dict(prefix_cache=True), "a recurrent state beside 3 layers' keys and values"),
    (dict(prefix_cache=True), "state snapshots"),
    (dict(prefix_cache=False, spec_k=2), "roll it back"),
    (dict(prefix_cache=False, tp=2), "no sharded form"),
])
def test_the_engine_refuses_what_a_state_cannot_do(knob, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(TINY, _params(), EngineConfig(**dict(ENGINE, **knob)))


def test_the_family_is_found_by_name_at_the_published_widths():
    from benchmark import harness as H
    from ray_tpu.serve.llm import _build_model, build_llm_app

    config = H.load_config(H.manifest(), "falcon-h1-34b-l8-1chip")
    assert sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    cfg = H.family_piece(config, "model_config")(H.sizes(config, False))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size,
            cfg.n_layers, cfg.d_ssm, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state,
            cfg.n_groups, cfg.d_conv, cfg.ssm_chunk) == (
                5120, 20, 4, 128, 21504, 65280, 8, 4096, 32, 128, 256, 2, 4, 128)
    assert cfg.ssm_segments() == (4096, 4096, 512, 512, 32) and cfg.conv_dim == 5120
    body = cfg.serving_body()
    assert body.kv_layout() == {"n_layers": 8, "n_heads": 4, "head_dim": 128,
                                "dtype": "bfloat16"}
    leaves = body.state_leaves(128)
    assert leaves == {"conv": (8, (3 * 5120,), "bfloat16"),
                      "ssd": (8, (32, 128, 256), "float32")}
    shapes = jax.eval_shape(lambda: falcon_h1_init(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 4.10e9 < n < 4.12e9  # 8 layers of 430.1M and 2 x 334M of vocabulary
    model = dataclasses.asdict(cfg)
    assert H.family_piece(config, "ssd_decode_state_bytes")(16, model) == (
        16 * 8 * 32 * 128 * 256 * 4 * 2)
    assert H.family_piece(config, "gqa_decode_kv_bytes")(1000, model) == 1000 * 8 * 2048
    got, _ = _build_model("falcon_h1", TINY, _params(), seed=0)
    assert got is TINY and build_llm_app(model="falcon_h1", model_cfg=TINY) is not None
    with pytest.raises(TypeError):
        _build_model("falcon_h1", object(), None, seed=0)
