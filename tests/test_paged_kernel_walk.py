"""The paged attention kernel walks the blocks a row HOLDS (ops/paged_attention.py).

Interpreted on the CPU, at pool shapes whose fetch run (``_run_blocks``) is
shorter than the table, so that a row of exactly a run, of a run plus one
token and of a full table all take different paths through the walk; then
lowered FOR a TPU at the served shapes (no chip, no libtpu), and run inside
a small engine whose batches differ in their longest row.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa

BS, D = 16, 256          # the served block: 16 tokens x 256, float32 here
LAYERS, LAYER = 2, 1     # a whole-pool view; the tables point into layer 1


def _batch(heads, w, seed=0):
    """A ragged batch in a whole-pool view: (q, k_pool, v_pool, tables with
    the layer's base added, positions, lengths, blocks per layer)."""
    run = pa._run_blocks(heads, BS, D, 4, 10**6)
    tmax = 2 * run + 2
    assert 1 < run < tmax, run
    # dead, shortest, exactly a block, exactly a run, a run plus one token,
    # two runs and a ragged tail, a full table
    lengths = np.array(
        [0, w, BS, run * BS, run * BS + 1, 2 * run * BS + 5, tmax * BS]
    )
    slots = len(lengths)
    held = -(-lengths // BS)
    nb = 1 + int(held.sum())        # block 0 of a layer is its trash block
    rng = np.random.RandomState(seed)
    order = 1 + rng.permutation(nb - 1)
    tables = np.zeros((slots, tmax), np.int32)
    at = 0
    for s, n in enumerate(held):
        tables[s, :n] = order[at:at + n]
        at += n
    shape = (LAYERS * nb, heads, BS, D)
    kp = jnp.asarray(rng.randn(*shape), jnp.float32)
    vp = jnp.asarray(rng.randn(*shape), jnp.float32)
    q = jnp.asarray(rng.randn(slots, w, heads, D), jnp.float32)
    positions = (lengths - w)[:, None] + np.arange(w)[None, :]
    return (q, kp, vp, jnp.asarray(tables + LAYER * nb),
            jnp.asarray(positions, jnp.int32), lengths, nb)


def _both(q, kp, vp, tables, positions, w):
    """(kernel, reference) outputs; decode goes through ``paged_attention``."""
    if w == 1:
        lens = positions[:, 0] + 1
        return (pa.paged_attention(q[:, 0], kp, vp, tables, lens, impl="pallas")[:, None],
                pa.paged_attention_xla(q[:, 0], kp, vp, tables, lens)[:, None])
    return (pa.paged_verify_attention(q, kp, vp, tables, positions, impl="pallas"),
            pa.paged_verify_attention_xla(q, kp, vp, tables, positions))


CASES = [(heads, w) for heads in (4, 16) for w in (1, 3, 4)]


@pytest.mark.parametrize("heads,w", CASES)
def test_walk_matches_xla_over_a_ragged_batch(heads, w):
    q, kp, vp, tables, positions, lengths, _ = _batch(heads, w)
    out, ref = _both(q, kp, vp, tables, positions, w)
    live = lengths > 0
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], atol=2e-5
    )
    assert np.isfinite(np.asarray(out)).all()   # the dead row too


@pytest.mark.parametrize("heads,w", CASES)
def test_walk_reads_nothing_a_row_does_not_hold(heads, w):
    """NaN in every block no row holds (each layer's trash block, all of
    the other layer) and in every block of a row past ITS OWN length changes
    nothing: no copy is issued for them."""
    q, kp, vp, tables, positions, lengths, nb = _batch(heads, w, seed=3)
    clean, ref = _both(q, kp, vp, tables, positions, w)
    held = np.zeros(LAYERS * nb, bool)
    t = np.asarray(tables)
    for s, n in enumerate(-(-lengths // BS)):
        held[t[s, :n]] = True
    poison = jnp.asarray(~held)[:, None, None, None]
    out, _ = _both(q, jnp.where(poison, jnp.nan, kp), jnp.where(poison, jnp.nan, vp),
                   tables, positions, w)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    live = lengths > 0
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], atol=2e-5
    )


@pytest.mark.parametrize("heads,want", [(16, 4), (4, 16)])
def test_run_is_a_function_of_the_block(heads, want):
    """About 512 KB of one pool a run at the served 16 x 256 bf16 blocks."""
    assert pa._run_blocks(heads, 16, 256, 2, 128) == want
    assert pa._run_blocks(heads, 16, 256, 2, 3) == 3     # never past the table


@pytest.mark.parametrize("slots,heads", [(32, 16), (64, 4)])
def test_served_shapes_lower_to_one_mosaic_kernel(monkeypatch, slots, heads):
    """Lowered FOR a TPU (lowering only) at the served shapes, one chip's and
    tp=4's: the decode attention is ONE Mosaic kernel, under the name the
    benchmark and the device trace look for."""
    from ray_tpu.util.device_prof import mosaic_kernels

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    pool = jax.ShapeDtypeStruct((2 * 1152, heads, 16, 256), jnp.bfloat16)
    lowered = jax.jit(
        lambda q, k, v, t, n: pa.paged_attention(q, k, v, t, n, impl="pallas")
    ).trace(
        jax.ShapeDtypeStruct((slots, heads, 256), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((slots, 128), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
    ).lower(lowering_platforms=("tpu",))
    assert mosaic_kernels(lowered) == ["paged_attention_decode"]


def test_decode_compiles_once_whatever_the_longest_row():
    """The bound on the walk is data inside the kernel, not a shape: batches
    whose longest row differs run ONE decode program."""
    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models.gptj import GPTJConfig, gptj_init

    cfg = GPTJConfig(
        vocab_size=128, seq_len=64, d_model=32, n_layers=2, n_heads=2,
        rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
        fused_loss=False,
    )
    params = gptj_init(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(cfg, params, EngineConfig(
        max_slots=3, num_blocks=32, block_size=4, max_blocks_per_seq=12,
        prefill_chunk=8, attn_impl="pallas",
    ))
    rng = np.random.RandomState(0)
    for n_prompt in (3, 9, 30):     # 1, 3 and 8 blocks at the first decode
        req = engine.submit(
            list(rng.randint(0, cfg.vocab_size, n_prompt)),
            SamplingParams(max_tokens=4),
        )
        for _ in range(200):
            if req.finished:
                break
            engine.step()
        assert req.finished
    stats = engine.stats()
    assert stats["retraces"] == 0
    decode = engine.runner.prof.stats()["decode"]
    assert decode["calls"] >= 9 and decode["cache_size"] == 1
