"""The layers the served families share (``ray_tpu/models/blocks.py``) and
the routed layer's ledger (``ray_tpu/ops/moe.py``), each against a plain
reading of what it says it computes.

The Mamba-2 mixer is held to a token-by-token float32 recurrence written
HERE in ``numpy`` (no chunk form, no kernel, no pool); the ledger to counts
made by hand over a mask with a dead row and an expert nobody chose; and the
three expert families' engines to ONE schema of ``stats()["moe"]``.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm.scheduler import SamplingParams
from ray_tpu.models import lfm2
from ray_tpu.models.blocks import Mamba2, dot32, rmsnorm
from ray_tpu.ops import moe

D, SLOTS, LAYERS = 12, 3, 2
MIXER = Mamba2(d_ssm=16, heads=4, d_state=8, n_groups=2, d_conv=4, eps=1e-5,
               dtype=jnp.dtype("float32"), sub=4, impl="xla")
WIDTH = MIXER.d_ssm + MIXER.conv_dim + MIXER.heads


def _mixer(scaled: bool) -> Mamba2:
    if not scaled:
        return MIXER
    return dataclasses.replace(
        MIXER, in_scale=np.linspace(0.5, 1.5, WIDTH).astype(np.float32))


@pytest.fixture(scope="module")
def layer():
    """One layer's seeded parameters (the family's own initializer's)."""
    stack = MIXER.init(tuple(jax.random.split(jax.random.PRNGKey(0), 6)), LAYERS, D,
                       D**-0.5, MIXER.d_ssm**-0.5, (1.0, 4.0), (0.05, 0.5))
    return jax.tree_util.tree_map(lambda a: a[1], stack)


def _inputs(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)


def _recurrence(mixer, layer, u):
    """The mixer over the tokens ``u`` (T, D) of one sequence from an empty
    state, one token at a time in float32: (outputs (T, D), the last d_conv -
    1 inputs of the convolution, the state (H, P, N))."""
    w = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), layer)
    H, N, G, K = mixer.heads, mixer.d_state, mixer.n_groups, mixer.d_conv
    P, ds = mixer.d_ssm // H, mixer.d_ssm
    silu = lambda v: v / (1.0 + np.exp(-v))  # noqa: E731
    scale = 1.0 if mixer.in_scale is None else mixer.in_scale
    tail = np.zeros((K - 1, mixer.conv_dim), np.float32)
    state, outs = np.zeros((H, P, N), np.float32), []
    for u_t in u:
        p = (u_t @ w["ssm_in"]["kernel"]) * scale
        z, raw, dt = p[:ds], p[ds:ds + mixer.conv_dim], p[ds + mixer.conv_dim:]
        window = np.concatenate([tail, raw[None]], axis=0)              # oldest first
        tail = window[1:]
        xbc = silu((window * w["conv"]["kernel"]).sum(axis=0) + w["conv"]["bias"])
        x = xbc[:ds].reshape(H, P)
        b, c = xbc[ds:ds + G * N].reshape(G, N), xbc[ds + G * N:].reshape(G, N)
        dt = np.logaddexp(0.0, dt + w["dt_bias"])
        a = -np.exp(w["A_log"])
        y = np.empty((H, P), np.float32)
        for h in range(H):
            g = h // (H // G)
            state[h] = np.exp(dt[h] * a[h]) * state[h] + dt[h] * np.outer(x[h], b[g])
            y[h] = state[h] @ c[g] + w["D"][h] * x[h]
        gated = (y.reshape(ds) * silu(z)).reshape(G, -1)
        normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + mixer.eps)
        outs.append((normed.reshape(ds) * w["ssm_norm"]["scale"]) @ w["ssm_out"]["kernel"])
    return np.stack(outs), tail, state


def _pools(mixer, fill=0.0):
    """The two state pools in the flat view a layer loop hands a mixer:
    ``LAYERS x (SLOTS + 1)`` slots, slot 0 of each layer the trash."""
    leaves = mixer.state_leaves(LAYERS, "float32")
    return tuple(jnp.full((n * (SLOTS + 1), *shape), fill, jnp.dtype(dt))
                 for n, shape, dt in leaves.values())


def _decodes(mixer, layer, conv, ssd, u, at):
    """``u`` (T, D) one token a step in batch row 1, beside a dead row on the
    trash slot."""
    outs = []
    for u_t in u:
        rows = jnp.stack([jnp.zeros(D), jnp.asarray(u_t)])
        out, conv, ssd = mixer.decode(
            rows, layer, conv, ssd, jnp.array([0, at]), jnp.array([False, True]))
        outs.append(np.asarray(out[1]))
    return np.stack(outs), conv, ssd


def _chunk(mixer, layer, conv, ssd, u, at, fresh, size=8):
    """``u`` (n <= size, D) as one chunk with a padded tail."""
    n = len(u)
    rows = np.concatenate([u, np.full((size - n, D), 7.0, np.float32)])
    out, conv, ssd = mixer.chunk(jnp.asarray(rows), layer, conv, ssd, at, jnp.asarray(fresh), n,
                                 jnp.arange(size) < n)
    return np.asarray(out[:n]), conv, ssd


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "in_scale"])
def test_the_decode_step_is_the_recurrence_and_a_dead_row_leaves_its_state(layer, scaled):
    mixer, u, at = _mixer(scaled), _inputs(9), 1 * (SLOTS + 1) + 2
    conv, ssd = _pools(mixer)
    got, conv, ssd = _decodes(mixer, layer, conv, ssd, u, at)
    want, tail, state = _recurrence(mixer, layer, u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(conv[at], tail.reshape(-1), rtol=1e-6)
    np.testing.assert_allclose(ssd[at], state, rtol=2e-4, atol=1e-6)
    # the dead row's slot of state, and every other slot, is as it was
    others = np.delete(np.arange(ssd.shape[0]), at)
    assert not np.asarray(ssd)[others].any()


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "in_scale"])
def test_the_chunk_step_is_the_recurrence_over_its_valid_rows(layer, scaled):
    mixer, u, at = _mixer(scaled), _inputs(6, seed=1), 2
    # a slot its last owner left full: a fresh chunk reads none of it
    conv, ssd = _pools(mixer, fill=3.0)
    got, conv, ssd = _chunk(mixer, layer, conv, ssd, u, at, fresh=True)
    want, tail, state = _recurrence(mixer, layer, u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(conv[at], tail.reshape(-1), rtol=1e-6)  # the last VALID inputs
    np.testing.assert_allclose(ssd[at], state, rtol=2e-4, atol=1e-6)
    assert (np.asarray(ssd)[np.delete(np.arange(ssd.shape[0]), at)] == 3.0).all()


@pytest.mark.parametrize("slot", ["fresh", "continued"])
def test_a_chunk_followed_by_decodes_equals_decodes_alone(layer, slot):
    u, at = _inputs(17, seed=2), 3
    alone, conv_a, ssd_a = _decodes(MIXER, layer, *_pools(MIXER), u, at)
    conv, ssd = _pools(MIXER, fill=3.0)
    if slot == "fresh":   # one chunk of 8, then 9 decodes
        first, conv, ssd = _chunk(MIXER, layer, conv, ssd, u[:8], at, fresh=True)
        got, n = [first], 8
    else:                 # a chunk, then a CONTINUED one with a padded tail, then 4 decodes
        first, conv, ssd = _chunk(MIXER, layer, conv, ssd, u[:8], at, fresh=True)
        second, conv, ssd = _chunk(MIXER, layer, conv, ssd, u[8:13], at, fresh=False)
        got, n = [first, second], 13
    rest, conv, ssd = _decodes(MIXER, layer, conv, ssd, u[n:], at)
    np.testing.assert_allclose(np.concatenate(got + [rest]), alone, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(conv[at], conv_a[at], rtol=1e-6)
    np.testing.assert_allclose(ssd[at], ssd_a[at], rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("d_conv", [2, 4])
def test_a_slot_s_row_holds_its_taps_oldest_first_and_a_decode_shifts_it_by_one_input(d_conv):
    mixer = dataclasses.replace(MIXER, d_conv=d_conv)
    stack = mixer.init(tuple(jax.random.split(jax.random.PRNGKey(1), 6)), LAYERS, D,
                       D**-0.5, mixer.d_ssm**-0.5, (1.0, 4.0), (0.05, 0.5))
    layer = jax.tree_util.tree_map(lambda a: a[0], stack)
    u, at, taps, cd = _inputs(6, seed=3), 2, d_conv - 1, mixer.conv_dim
    raw = np.asarray(mixer.project(jnp.asarray(u), layer)[1])       # what the convolution sees
    conv, ssd = _pools(mixer, fill=3.0)
    assert conv.shape == (LAYERS * (SLOTS + 1), taps * cd)          # ONE row a slot
    _, conv, ssd = _chunk(mixer, layer, conv, ssd, u[:5], at, fresh=True)
    row = np.asarray(conv[at])
    np.testing.assert_allclose(row, raw[5 - taps:5].reshape(-1), rtol=1e-6)
    _, after, _ = _decodes(mixer, layer, conv, ssd, u[5:], at)
    after = np.asarray(after[at])
    np.testing.assert_array_equal(after[:(taps - 1) * cd], row[cd:])  # by exactly conv_dim
    np.testing.assert_allclose(after[(taps - 1) * cd:], raw[5], rtol=1e-6)
    others = np.delete(np.arange(conv.shape[0]), [0, at])           # 0: the dead row's trash
    assert (np.asarray(conv)[others] == 3.0).all()


def test_rmsnorm_and_dot32_give_float32_whatever_comes_in():
    x = jnp.asarray(_inputs(3), jnp.bfloat16)
    scale = jnp.asarray(np.linspace(0.5, 2.0, D), jnp.bfloat16)
    got = rmsnorm(x, scale, 1e-5)
    x32, s32 = np.asarray(x, np.float32), np.asarray(scale, np.float32)
    want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-5) * s32
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)
    out = dot32(x, jnp.ones((D, 5), jnp.float32))   # the product on x's dtype, the sum in float32
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, np.repeat(x32.sum(-1, keepdims=True), 5, 1), rtol=1e-6)


# -- the routed layer's ledger ---------------------------------------------------------------


def _mask(n, held=6, seed=0):
    """(n, held) pairs with row 2 DEAD and expert 4 chosen by nobody."""
    mask = np.random.default_rng(seed).random((n, held)) < 0.4
    mask[2], mask[:, 4] = False, False
    mask[0, 1] = True
    return mask


@pytest.mark.parametrize("phase, n", [("decode", 5), ("decode", moe.TILE), ("chunk", 150)])
def test_count_routed_counts_what_numpy_counts(phase, n):
    mask, names = _mask(n), moe.COUNTERS
    start = np.arange(len(names) + 6, dtype=np.int32)         # counts ADD to what is there
    got = np.asarray(moe.count_routed(jnp.asarray(start), jnp.asarray(mask), phase)) - start
    load = mask.sum(axis=0)
    touched = int((load > 0).sum())
    assert load[4] == 0 and touched < 6
    # the form that runs: all n rows a touched expert, or its pairs in whole blocks
    rows = n if n <= moe.TILE else moe.row_block(n)
    want = {f"{phase}_pairs": int(mask.sum()), f"{phase}_touched": touched,
            f"{phase}_tile_rows": int((-(-load // rows)).sum()) * rows,
            f"{phase}_expert_steps": touched}
    assert {name: int(got[i]) for i, name in enumerate(names)} == {
        name: want.get(name, 0) for name in names}
    np.testing.assert_array_equal(got[len(names):], load)


def test_the_ledger_reads_back_under_its_names():
    (shape,) = moe.counters_shape(6)
    assert shape.shape == (1, len(moe.COUNTERS) + 6) and shape.dtype == jnp.int32
    counts = jnp.zeros(shape.shape, shape.dtype)
    counts = moe.count_step(moe.count_step(moe.count_step(counts, "decode"), "chunk"), "decode")
    flat = moe.count_routed(counts.reshape(-1), jnp.asarray(_mask(5)), "decode")
    got = moe.read_counters((flat.reshape(shape.shape),))["moe"]
    assert list(got) == [*moe.COUNTERS, "load"]
    assert got["decodes"] == 2 and got["chunks"] == 1 and got["chunk_pairs"] == 0
    assert sum(got["load"]) == got["decode_pairs"] == int(_mask(5).sum())


def _tiny(family: str):
    """(configuration, parameters, engine sizes) of a family's parity file."""
    parity = importlib.import_module(f"test_llm_{family}_parity")
    return parity.TINY, parity._params(), dict(parity.ENGINE, prefix_cache=False)


@pytest.mark.parametrize("family", ["kimi", "granite_h", "lfm2"])
def test_the_three_expert_families_count_under_one_schema(family):
    assert lfm2.COUNTERS is moe.COUNTERS
    cfg, params, sizes = _tiny(family)
    eng = LLMEngine(cfg, params, EngineConfig(**sizes))
    eng.generate([int(t) for t in np.random.default_rng(3).integers(1, cfg.vocab_size, 19)],
                 SamplingParams(max_tokens=5))
    got = eng.stats()["moe"]
    assert list(got) == [*moe.COUNTERS, "load"] and len(got["load"]) == cfg.experts_held
    assert sum(got["load"]) == got["decode_pairs"] + got["chunk_pairs"] > 0
    assert got["chunks"] == 3 and got["decodes"] == 4
    # both phases count their touched experts and computed rows, in every family
    for phase in ("decode", "chunk"):
        assert 0 < got[f"{phase}_touched"] <= got[f"{phase}_pairs"] <= got[f"{phase}_tile_rows"]
    # the slots and the chunk are no more than a tile: the batch form ran in both
    assert got["decode_expert_steps"] == got["decode_touched"]
    assert got["chunk_expert_steps"] == 0
