"""The paged runners against the PLAIN reference, logit for logit.

``benchmark/reference/gptj.py`` is the benchmark's yardstick for GPT-J:
one full forward pass in straight ``jax.numpy`` at float32, no cache, no
kernels, nothing from ``ray_tpu``'s model code.  Every other tier-1 test
compares the runners with each other or with ``models.gptj`` (which
shares their layer math); this one holds ``PagedModelRunner`` and the
tensor-parallel runner (``llm.multichip``, tp 2 and 4 on the conftest's
host-platform devices) to the reference itself: a prompt prefilled in
two chunks, then six greedy decode steps through the paged (under tp:
head-sharded) pool.

LOGITS are compared, not tokens: with random weights the largest logit
changes on rounding.  Prefill returns its last row's logits; decode
returns the chosen token and its ``logp``, which must equal the
reference's ``log_softmax`` there, and the reference's logit of that
token must be its maximum.

``TOL`` is 1e-4: everything is float32, and what differs from the
reference is the ORDER of additions (the cache splits the softmax's
sums by block, ``_tp_sum`` splits the two row-parallel contractions by
device), a few units in the last place of logits of size ~3.5: measured
2e-6 at every ``tp``.  A path computed in bfloat16 misses the reference
by 2.3e-2 and two layers swapped by 3.9: both are cases below, and
both must FAIL the tolerance, so it cannot be met by a lower precision
or a wrong layer.
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

from benchmark.reference import gptj as reference  # noqa: E402
from ray_tpu.llm.cache import CacheConfig, KVBlockPool  # noqa: E402
from ray_tpu.llm.model_runner import (  # noqa: E402
    PagedModelRunner,
    host_batch,
    pack_knobs,
)
from ray_tpu.llm.multichip import (  # noqa: E402
    ShardedKVBlockPool,
    TensorParallelPagedModelRunner,
)
from ray_tpu.models.gptj import GPTJConfig, gptj_init  # noqa: E402

TOL = 1e-4

# tp=4-divisible: 4 heads x 16, d_ff 256; three layers so that a swap shows
TINY = GPTJConfig(
    vocab_size=128, seq_len=64, d_model=64, n_layers=3, n_heads=4,
    rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
    fused_loss=False,
)
BLOCK, CHUNK, SLOTS, TABLE = 4, 8, 3, 12
PROMPT_LEN, N_DECODE, SLOT = 13, 6, 1  # 8 + 5 rows: two chunks, the second ragged


@functools.lru_cache(maxsize=1)
def _params():
    """Seeded random weights with every bias and layernorm scale moved off
    its initial 0 / 1: a bias added once per device instead of once, or
    left out, must show."""
    params = gptj_init(jax.random.PRNGKey(26), TINY)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(27), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        if getattr(path[-1], "key", None) in ("bias", "scale"):
            leaf = leaf + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def _prompt():
    return np.random.default_rng(26).integers(1, TINY.vocab_size, PROMPT_LEN).tolist()


def _reference(params, tokens, rows):
    return np.asarray(
        reference.logits_at(params, tokens, rows, TINY.n_heads, TINY.rotary_dim)
    )


@functools.lru_cache(maxsize=None)
def _served(tp: int, dtype: str = "float32"):
    """Prefill in two chunks, then ``N_DECODE`` greedy steps, on a fresh
    runner and pool.  Returns (tokens fed in all, the two chunks' last-row
    logits, the decode steps' tokens, their logps)."""
    cfg = dataclasses.replace(TINY, dtype=dtype)
    cache = CacheConfig(num_blocks=32, block_size=BLOCK, max_blocks_per_seq=TABLE)
    shape = dict(n_layers=cfg.n_layers, n_heads=cfg.n_heads, head_dim=cfg.head_dim,
                 dtype=dtype)
    if tp == 1:
        runner = PagedModelRunner(cfg, _params(), BLOCK, attn_impl="xla")
        pool = KVBlockPool(cache, **shape)
    else:
        runner = TensorParallelPagedModelRunner(cfg, _params(), BLOCK, attn_impl="xla", tp=tp)
        pool = ShardedKVBlockPool(cache, tp=tp, **shape)
    prompt = _prompt()
    assert pool.allocate("s", PROMPT_LEN + N_DECODE + 1)
    table = pool.table_row("s")
    k, v, chunk_logits = pool.k, pool.v, []
    for start in range(0, PROMPT_LEN, CHUNK):
        piece = prompt[start:start + CHUNK]
        tokens = np.zeros(CHUNK, np.int32)
        tokens[:len(piece)] = piece
        k, v, logits, tok, _ = runner.prefill_chunk(
            k, v, tokens, start, len(piece), table, pack_knobs(0, 0.0, 0, 1.0, 0))
        assert int(tok[0]) == int(np.asarray(logits).argmax())  # the in-program sampler's row
        chunk_logits.append(np.asarray(logits, np.float32))
    seq = prompt + [int(chunk_logits[-1].argmax())]
    tables = np.zeros((SLOTS, TABLE), np.int32)  # idle rows write the trash block
    tables[SLOT] = table
    zeros = np.zeros(SLOTS, np.int32)
    logps = []
    for _ in range(N_DECODE):
        tokens, positions = zeros.copy(), zeros.copy()
        tokens[SLOT], positions[SLOT] = seq[-1], len(seq) - 1
        k, v, _carry, nxt, logp = runner.decode_step(
            k, v, *host_batch(
                tokens, positions, tables,
                np.zeros(SLOTS, np.float32), zeros, np.ones(SLOTS, np.float32),
                np.zeros(SLOTS, np.uint32), zeros,
            ),
        )
        seq.append(int(nxt[SLOT]))
        logps.append(float(logp[SLOT]))
    if tp > 1:
        stats = runner.tp_sum_stats()
        row = TINY.d_model * 4 * (tp - 1) * TINY.n_layers  # bytes a row, all layers
        assert stats["per_step"] == {
            "prefill": {"calls": TINY.n_layers, "bytes": CHUNK * row},
            "decode": {"calls": TINY.n_layers, "bytes": SLOTS * row},
        }
        assert stats["calls"] == TINY.n_layers * (2 + N_DECODE)
        assert stats["bytes"] == (2 * CHUNK + N_DECODE * SLOTS) * row
    return seq, chunk_logits, seq[PROMPT_LEN + 1:], logps


@pytest.fixture(scope="module", autouse=True)
def _enough_devices():
    if len(jax.devices("cpu")) < 4:
        pytest.skip("needs a >=4-device CPU mesh (conftest's XLA_FLAGS)")


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_chunked_prefill_logits_match_reference(tp):
    seq, chunk_logits, _, _ = _served(tp)
    want = _reference(_params(), seq[:PROMPT_LEN], [CHUNK - 1, PROMPT_LEN - 1])
    assert np.abs(want).max() > 1.0  # logits of a size that makes TOL mean something
    for got, ref in zip(chunk_logits, want):
        assert np.abs(got - ref).max() <= TOL


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_decode_through_the_cache_matches_reference(tp):
    seq, _, chosen, logps = _served(tp)
    rows = list(range(PROMPT_LEN, PROMPT_LEN + N_DECODE))
    # teacher-forced: the reference reads the prompt and the engine's own
    # tokens in ONE full forward pass; row p predicts the token at p + 1
    want = _reference(_params(), seq[:-1], rows)
    want_logp = np.asarray(jax.nn.log_softmax(jnp.asarray(want), axis=-1))
    at = np.arange(N_DECODE)
    assert np.abs(want_logp[at, chosen] - np.asarray(logps)).max() <= TOL
    # greedy: the reference's logit of the chosen token is its largest
    assert (want.max(axis=-1) - want[at, chosen]).max() <= TOL


def test_a_bfloat16_path_fails_the_tolerance():
    seq, chunk_logits, _, _ = _served(1, "bfloat16")
    want = _reference(_params(), seq[:PROMPT_LEN], [CHUNK - 1, PROMPT_LEN - 1])
    assert max(np.abs(g - r).max() for g, r in zip(chunk_logits, want)) > 10 * TOL


def test_swapped_layers_fail_the_tolerance():
    seq, chunk_logits, _, _ = _served(1)
    swapped = dict(_params())
    swapped["blocks"] = jax.tree_util.tree_map(
        lambda a: a[jnp.asarray([1, 0, 2])], swapped["blocks"])
    want = _reference(swapped, seq[:PROMPT_LEN], [CHUNK - 1, PROMPT_LEN - 1])
    assert max(np.abs(g - r).max() for g, r in zip(chunk_logits, want)) > 10 * TOL
