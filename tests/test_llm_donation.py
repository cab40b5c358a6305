"""RL013's runtime twin: the use-after-donation bug class is REAL.

``PagedModelRunner`` jits its decode/prefill/verify/fork steps with
``donate_argnums`` on the KV pool buffers (model_runner.py) — each step
scatters into the pool in place instead of copying the biggest array in
inference. The price is the RL013 contract: the moment a step call
dispatches, XLA invalidates the INPUT buffers; any read of the old
``pool.k``/``pool.v`` reference before the engine reassigns them is a
deleted-buffer error (or, on backends that alias without deleting,
silently garbled data).

This module drives the real jitted paged-decode path and pins both
directions, exactly like ``tests/test_llm_weight_swap.py`` twins RL009:

* the pre-call buffer object IS deleted after the call — reading it
  raises — which is the poisoned state RL013's dataflow models;
* the engine's reassign-immediately idiom (``self.pool.k, self.pool.v =
  k, v``) keeps the pool usable and decoding deterministic across
  repeated donated steps, which is the fix the rule's message demands.

Backends may legally ignore donation (older CPU runtimes warn and copy);
a probe skips the strict deletion asserts there so the suite stays
honest about what it proved.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm.cache import CacheConfig, KVBlockPool  # noqa: E402
from ray_tpu.llm.model_runner import (  # noqa: E402
    PagedModelRunner,
    host_batch,
    pack_knobs,
)
from ray_tpu.models.gpt import GPTConfig, gpt_init  # noqa: E402


def _donation_effective() -> bool:
    """Does this backend actually invalidate donated buffers?"""
    x = jnp.arange(4.0)
    jax.jit(lambda a: a + 1, donate_argnums=(0,))(x)
    return x.is_deleted()


DONATION_EFFECTIVE = _donation_effective()

needs_donation = pytest.mark.skipif(
    not DONATION_EFFECTIVE,
    reason="backend ignores buffer donation (copies instead); the "
    "use-after-donation failure mode cannot manifest here",
)

CFG = GPTConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, seq_len=64,
    dtype="float32",
)


def _runner_and_pool(num_blocks=8, block_size=4, tmax=4):
    params = gpt_init(jax.random.PRNGKey(0), CFG)
    runner = PagedModelRunner(CFG, params, block_size)
    pool = KVBlockPool(
        CacheConfig(
            num_blocks=num_blocks, block_size=block_size,
            max_blocks_per_seq=tmax,
        ),
        n_layers=CFG.n_layers, n_heads=CFG.n_heads, head_dim=CFG.head_dim,
    )
    return runner, pool


def _decode_args(pool, slots=2):
    """(tokens, positions, tables, temp, top_k, top_p, seeds, counters)
    for a greedy decode step with one live block per slot."""
    tables = np.zeros((slots, pool.cfg.max_blocks_per_seq), np.int32)
    tables[:, 0] = 1
    return (
        np.array([3, 5][:slots], np.int32),        # tokens
        np.zeros(slots, np.int32),                 # positions
        tables,
        np.zeros(slots, np.float32),               # temp (greedy)
        np.zeros(slots, np.int32),                 # top_k
        np.ones(slots, np.float32),                # top_p
        np.zeros(slots, np.uint32),                # seeds
        np.zeros(slots, np.int32),                 # counters
    )


@needs_donation
def test_decode_step_invalidates_donated_pool_buffers():
    """The fixture RL013 mirrors (test_raylint.RL013_ENGINE_BAD), run for
    real: keep the old pool.k reference across a decode_step and the read
    blows up with a deleted-buffer error."""
    runner, pool = _runner_and_pool()
    stale_k, stale_v = pool.k, pool.v
    carry, *rest = host_batch(*_decode_args(pool))
    carry = runner.place(carry)  # the slot state rides the same contract
    k, v, new_carry, nxt, logp = runner.decode_step(pool.k, pool.v, carry, *rest)
    assert stale_k.is_deleted() and stale_v.is_deleted() and carry.is_deleted()
    assert np.asarray(new_carry)[0].tolist() == np.asarray(nxt).tolist()
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(stale_k)  # the poisoned read RL013 flags statically
    # the reassign-immediately idiom restores a usable pool
    pool.k, pool.v = k, v
    assert np.asarray(pool.k).shape == stale_k.shape
    assert int(nxt[0]) >= 0


@needs_donation
def test_prefill_and_fork_paths_also_donate():
    """Every jitted pool path donates, not just decode — the rule's
    summary machinery covers prefill_chunk and fork_blocks callers too."""
    runner, pool = _runner_and_pool()
    table = pool.table_row(None)
    table[0] = 1
    old_k = pool.k
    k, v, logits, tok, _logp = runner.prefill_chunk(
        pool.k, pool.v, np.array([1, 2, 3, 0], np.int32), 0, 3, table,
        pack_knobs(0, 0.0, 0, 1.0, 0),
    )
    assert int(tok[0]) == int(np.asarray(logits).argmax())
    assert old_k.is_deleted()
    pool.k, pool.v = k, v
    old_k = pool.k
    z = np.zeros(2, np.int32)
    pool.k, pool.v = runner.fork_blocks(pool.k, pool.v, z, z)
    assert old_k.is_deleted()
    assert logits.shape == (CFG.vocab_size,)


def test_reassigned_pool_decodes_deterministically():
    """Donation with immediate reassignment (the pattern RL013 enforces)
    is semantically clean: two identical fresh runs produce identical
    tokens and logprobs across repeated donated steps. Runs on every
    backend — donating or copying, the OUTPUT contract holds."""

    def run():
        runner, pool = _runner_and_pool()
        out = []
        for step in range(3):
            tokens, positions, tables, temp, tk, tp, seeds, counters = (
                _decode_args(pool)
            )
            positions[:] = step
            counters[:] = step
            k, v, _carry, nxt, logp = runner.decode_step(
                pool.k, pool.v, *host_batch(
                    tokens, positions, tables, temp, tk, tp, seeds, counters
                ),
            )
            pool.k, pool.v = k, v
            out.append((np.asarray(nxt).copy(), np.asarray(logp).copy()))
        return out

    a, b = run(), run()
    for (ta, la), (tb, lb) in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
        np.testing.assert_allclose(la, lb, rtol=1e-6)


def test_donation_probe_matches_platform_expectation():
    """The probe itself is pinned so a jax upgrade that changes donation
    semantics surfaces here, not as silent skips: on the CPU backend
    donation IS effective, and the skip branch above should be dead in
    CI."""
    assert isinstance(DONATION_EFFECTIVE, bool)
    if jax.default_backend() == "cpu":
        assert DONATION_EFFECTIVE, (
            "CPU jax stopped honoring donate_argnums — the donated paged "
            "paths (model_runner.py) silently became copies; re-measure "
            "the pool-update cost before trusting this"
        )
