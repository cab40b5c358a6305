"""RL009's runtime twin: a params hot-swap must reach EVERY jitted
model-runner entry point.

The PR 7 latent bug was exactly this failing silently: ``_embed``/
``_lm_head`` read ``self.params`` at trace time, so the embed/pos/ln_f/
lm_head weights were baked into the compiled executables and
``LLMEngine.update_weights`` swapped only the layer stack. raylint RL009
now catches that shape statically; this suite is the dynamic guard — it
swaps ``runner.params`` (the same untraced attribute assignment
``update_weights`` performs, of what ``prepare_params`` makes of the new
tree) and asserts each jitted path (``prefill_chunk``, ``decode_step``,
``verify_step``) produces outputs identical to a FRESH runner built from
the swapped params, and different from the pre-swap outputs.
``fork_blocks`` is asserted params-independent (a pure device block copy)
so all four entry points are pinned.

Since PR 65 a GPT-J runner keeps q / k / v as ONE leaf, so the tree a
caller swaps in is not the resident tree's shape: ``update_weights`` holds
it to what the runner was GIVEN (``runner.given``), casts, and the runner
packs and places it; on one chip and under ``tp=2`` (host devices) the
swap retraces nothing and a wrong tree is refused as before.
"""

import dataclasses

import numpy as np
import pytest

import jax

from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm.cache import CacheConfig, KVBlockPool
from ray_tpu.llm.model_runner import PagedModelRunner, host_batch, pack_knobs
from ray_tpu.llm.multichip import ShardedKVBlockPool, TensorParallelPagedModelRunner
from ray_tpu.models.gptj import GPTJConfig, gptj_init

CFG = GPTJConfig(
    vocab_size=64, seq_len=64, d_model=32, n_layers=2, n_heads=2,
    rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
    fused_loss=False,
)
BLOCK_SIZE = 4
SLOTS = 2
SPEC_W = 3  # verify window: 1 emitted + 2 drafted


@pytest.fixture(scope="module")
def params_pair():
    a = gptj_init(jax.random.PRNGKey(0), CFG)
    b = gptj_init(jax.random.PRNGKey(1), CFG)
    return a, b


TP = [1, pytest.param(2, marks=pytest.mark.skipif(
    len(jax.devices("cpu")) < 2, reason="needs 2 host devices (conftest's XLA_FLAGS)"))]


def _runner(params, tp, cfg=CFG):
    if tp == 1:
        return PagedModelRunner(cfg, params, BLOCK_SIZE, attn_impl="xla")
    return TensorParallelPagedModelRunner(cfg, params, BLOCK_SIZE, attn_impl="xla", tp=tp)


def _pool(runner):
    cache = CacheConfig(num_blocks=16, block_size=BLOCK_SIZE, max_blocks_per_seq=8)
    shape = dict(n_layers=CFG.n_layers, n_heads=CFG.n_heads, head_dim=CFG.head_dim,
                 dtype=runner.cfg.dtype)
    tp = getattr(runner, "tp", 1)
    return KVBlockPool(cache, **shape) if tp == 1 else ShardedKVBlockPool(cache, tp=tp, **shape)


def _drive(runner):
    """One prefill chunk + one batched decode + one verify window against a
    fresh pool; returns every jitted entry point's observable output."""
    pool = _pool(runner)
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, CFG.vocab_size, 8).astype(np.int32)
    pool.allocate("s0", 12)
    pool.allocate("s1", 12)
    table0 = pool.table_row("s0")

    k, v, last_logits, _tok, _lp = runner.prefill_chunk(
        pool.k, pool.v, prompt, 0, len(prompt), table0,
        pack_knobs(0, 0.0, 0, 1.0, 0),
    )
    pool.k, pool.v = k, v

    tables = np.stack([pool.table_row("s0"), pool.table_row("s1")])
    tokens = np.array([prompt[-1], prompt[0]], np.int32)
    positions = np.array([len(prompt), 0], np.int32)
    greedy = np.zeros(SLOTS, np.float32)
    top_k = np.zeros(SLOTS, np.int32)
    top_p = np.ones(SLOTS, np.float32)
    seeds = np.zeros(SLOTS, np.uint32)
    counters = np.zeros(SLOTS, np.int32)
    k, v, _carry, nxt, logp = runner.decode_step(
        pool.k, pool.v, *host_batch(
            tokens, positions, tables, greedy, top_k, top_p, seeds, counters
        ),
    )
    pool.k, pool.v = k, v

    win = np.tile(prompt[:SPEC_W], (SLOTS, 1)).astype(np.int32)
    base_pos = np.array([len(prompt) + 1, 1], np.int32)
    k, v, n_acc, out, out_lp = runner.verify_step(
        pool.k, pool.v, win, base_pos, tables,
        greedy, top_k, top_p, seeds, counters,
    )
    pool.k, pool.v = k, v
    return {
        "prefill_logits": np.asarray(last_logits),
        "decode_tokens": np.asarray(nxt),
        "decode_logprobs": np.asarray(logp),
        "verify_accepted": np.asarray(n_acc),
        "verify_tokens": np.asarray(out),
        "verify_logprobs": np.asarray(out_lp),
        "pool": pool,
        "runner": runner,
    }


@pytest.mark.parametrize("tp", TP)
def test_every_jitted_entry_point_reflects_param_swap(params_pair, tp):
    params_a, params_b = params_pair
    runner = _runner(params_a, tp)
    before = _drive(runner)

    # the exact swap update_weights performs: reassign the attribute, no
    # re-jit — the executables must pick up the new params via the traced
    # argument, or this whole test is comparing stale constants
    runner.params = runner.prepare_params(params_b)
    after = _drive(runner)
    fresh = _drive(_runner(params_b, tp))

    for key in (
        "prefill_logits", "decode_tokens", "decode_logprobs",
        "verify_accepted", "verify_tokens", "verify_logprobs",
    ):
        np.testing.assert_allclose(
            after[key], fresh[key], rtol=1e-5, atol=1e-5,
            err_msg=f"{key}: swapped runner diverges from fresh runner — "
            "some weights are baked into the jitted executable",
        )
    # and the swap must actually CHANGE the outputs, or the assertions
    # above would pass vacuously on params-independent garbage
    assert not np.allclose(before["prefill_logits"], after["prefill_logits"])
    assert not np.allclose(before["decode_logprobs"], after["decode_logprobs"])


def test_fork_blocks_is_params_independent(params_pair):
    params_a, params_b = params_pair
    runner = PagedModelRunner(CFG, params_a, BLOCK_SIZE, attn_impl="xla")
    state = _drive(runner)
    pool = state["pool"]
    src_block = pool.blocks_of("s0")[0]
    dst_block = pool.blocks_of("s1")[-1]
    lanes_src = np.zeros(SLOTS, np.int32)
    lanes_dst = np.zeros(SLOTS, np.int32)
    lanes_src[0], lanes_dst[0] = src_block, dst_block

    runner.params = runner.prepare_params(params_b)  # swap BEFORE the fork: the copy must not care
    k, v = runner.fork_blocks(pool.k, pool.v, lanes_src, lanes_dst)
    k = np.asarray(k)
    v = np.asarray(v)
    np.testing.assert_array_equal(k[:, dst_block], k[:, src_block])
    np.testing.assert_array_equal(v[:, dst_block], v[:, src_block])


def _cache_sizes(runner) -> dict:
    return {n: getattr(runner, n)._cache_size()
            for n in ("_prefill", "_decode", "_verify", "_fork")}


@pytest.mark.parametrize("tp", TP)
def test_update_weights_takes_float32_q_k_v_without_a_retrace(params_pair, tp):
    """A learner's tree (separate ``q`` / ``k`` / ``v`` leaves, float32
    masters, on the host) into a bfloat16 engine: cast, packed and placed
    by ``prepare_params``; every entry point then computes what a runner
    BORN with those weights computes, bit for bit, and no program was
    traced again."""
    params_a, params_b = params_pair
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    born = lambda tree: jax.tree_util.tree_map(lambda x: x.astype("bfloat16"), tree)  # noqa: E731
    eng = LLMEngine(cfg, born(params_a), EngineConfig(
        tp=tp, max_slots=SLOTS, num_blocks=16, block_size=BLOCK_SIZE,
        max_blocks_per_seq=8, prefill_chunk=8, attn_impl="xla"))
    runner = eng.runner
    before = _drive(runner)
    traced = _cache_sizes(runner)

    assert eng.update_weights(jax.device_get(params_b)) == 1
    blocks = runner.params["blocks"]
    assert "attn_qkv" in blocks and not {"q", "k", "v"} & set(blocks)
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves(runner.params)} == {"bfloat16"}
    after = _drive(runner)
    assert _cache_sizes(runner) == traced, "the swap retraced a step"
    fresh = _drive(_runner(born(params_b), tp, cfg))
    for key in ("prefill_logits", "decode_tokens", "decode_logprobs",
                "verify_accepted", "verify_tokens", "verify_logprobs"):
        np.testing.assert_array_equal(
            np.asarray(after[key], np.float32), np.asarray(fresh[key], np.float32), err_msg=key)
    assert not np.array_equal(np.asarray(before["prefill_logits"], np.float32),
                              np.asarray(after["prefill_logits"], np.float32))


@pytest.mark.parametrize("tp", TP)
def test_update_weights_refuses_another_shape_or_structure(params_pair, tp):
    """Held to the GIVEN tree: a leaf of another shape and the resident
    (packed) tree itself are both refused, with the messages they had."""
    params_a, _ = params_pair
    eng = LLMEngine(CFG, params_a, EngineConfig(
        tp=tp, max_slots=SLOTS, num_blocks=16, block_size=BLOCK_SIZE,
        max_blocks_per_seq=8, prefill_chunk=8, attn_impl="xla"))
    wide = jax.tree_util.tree_map(np.asarray, params_a)
    wide["blocks"]["q"]["kernel"] = np.zeros((CFG.n_layers, CFG.d_model, 2 * CFG.d_model), np.float32)
    with pytest.raises(ValueError, match="update_weights leaf mismatch"):
        eng.update_weights(wide)
    with pytest.raises(ValueError, match="update_weights pytree structure mismatch"):
        eng.update_weights(eng.runner.params)
    assert eng.weights_version == 0
