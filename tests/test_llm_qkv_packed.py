"""A GPT-J layer's q / k / v kernels are ONE leaf and ONE product in the
paged runners (PR 65; beside ``test_llm_pool_inplace.py``, which reads the
same programs for their temporaries).

The runner is GIVEN ``blocks.{q, k, v}.kernel`` ``(L, d, d)`` and keeps
``blocks.attn_qkv.kernel`` ``(L, d, 3d)`` (``prepare_params``): on a v5e
three products of three sliced leaves each cost a copy of the slice into
fast memory and a re-laid copy of that, one product of one leaf reads its
slice inside the product (PERF.md section 6, PR 65).  Every program, on one
chip and under ``tp=2`` on host devices, is held to three things:

* (a) the resident tree holds the packed leaf and no ``q`` / ``k`` / ``v``,
  and the given tree's bytes: the kernels are on the device once;
* (b) the lowered program has ONE ``dot_general`` under the ``qkv`` scope
  (the layer is traced once for all layers), not three;
* (c) the tokens are the dense twin's (``models.gptj.gptj_forward``, which
  keeps its three kernels), greedy, and what a row returns does not depend
  on the rows beside it: the same row among other neighbours (other slots'
  tokens, a chunk's padded tail) returns the same bits.
"""

import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm.cache import CacheConfig, KVBlockPool  # noqa: E402
from ray_tpu.llm.model_runner import PagedModelRunner, host_batch, pack_knobs  # noqa: E402
from ray_tpu.llm.multichip import (  # noqa: E402
    ShardedKVBlockPool,
    TensorParallelPagedModelRunner,
)
from ray_tpu.models.gptj import GPTJConfig, gptj_forward, gptj_init  # noqa: E402

TINY = GPTJConfig(
    vocab_size=96, seq_len=64, d_model=64, n_layers=2, n_heads=4, rotary_dim=8,
    dtype="float32", remat=False, attn_impl="xla", fused_loss=False,
)
BLOCK, CHUNK, SLOTS, W, TABLE, NB = 4, 8, 3, 3, 8, 40
N_VALID = 5  # of a chunk's 8 rows: three padded
GREEDY = (
    np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32), np.ones(SLOTS, np.float32),
    np.zeros(SLOTS, np.uint32), np.zeros(SLOTS, np.int32),
)
KNOBS = pack_knobs(0, 0.0, 0, 1.0, 0)


@functools.lru_cache(maxsize=1)
def _params():
    return gptj_init(jax.random.PRNGKey(65), TINY)


@functools.lru_cache(maxsize=None)
def _runner(tp):
    if tp == 1:
        return PagedModelRunner(TINY, _params(), BLOCK, "xla")
    return TensorParallelPagedModelRunner(TINY, _params(), BLOCK, "xla", tp=tp)


def _pools(tp):
    cache = CacheConfig(num_blocks=NB, block_size=BLOCK, max_blocks_per_seq=TABLE)
    shape = dict(n_layers=TINY.n_layers, n_heads=TINY.n_heads, head_dim=TINY.head_dim,
                 dtype=TINY.dtype)
    pool = KVBlockPool(cache, **shape) if tp == 1 else ShardedKVBlockPool(cache, tp=tp, **shape)
    return pool.k, pool.v


def _greedy(tokens) -> list:
    """The dense twin's greedy token after every prefix of ``tokens``."""
    logits = gptj_forward(TINY, _params(), jnp.asarray(tokens, jnp.int32)[None, :])
    return np.asarray(logits[0]).argmax(axis=-1).tolist()


def _tables():
    """Row ``s`` owns blocks ``1 + s * TABLE ...``: no two rows share one."""
    return 1 + np.arange(SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)


def _decode_ops(others):
    """Every slot feeds one token at position 0; slot 0's is 7, the others'
    are drawn by ``others``."""
    tokens = np.r_[7, np.random.default_rng(others).integers(1, 96, SLOTS - 1)]
    zeros = np.zeros(SLOTS, np.int32)
    return host_batch(tokens.astype(np.int32), zeros, _tables(), *GREEDY)


def _chunk_ops(others):
    """``N_VALID`` fixed tokens of one sequence from position 0 in the
    blocks after the slots', the padded tail drawn by ``others``."""
    tokens = np.r_[np.arange(11, 11 + N_VALID),
                   np.random.default_rng(others).integers(1, 96, CHUNK - N_VALID)]
    table = 1 + SLOTS * TABLE + np.arange(TABLE, dtype=np.int32)
    return (tokens.astype(np.int32), np.int32(0), np.int32(N_VALID), table, KNOBS)


def _verify_ops(others):
    """Slot 0 feeds 7 and the dense twin's next two tokens as drafts (all
    accepted, so its row is the twin's continuation); the others' windows
    are drawn by ``others``."""
    window = [7] + _greedy([7])[-1:]
    window += _greedy(window)[-1:]
    tokens = np.vstack([window, np.random.default_rng(others).integers(1, 96, (SLOTS - 1, W))])
    return (tokens.astype(np.int32), np.zeros(SLOTS, np.int32), _tables(), *GREEDY)


#: program -> (jitted attribute, operands after the pools by ``others``,
#: what slot 0 / the chunk returns (compared bit for bit across neighbours),
#: those tokens by the dense twin)
PROGRAMS = {
    "decode": (
        "_decode", _decode_ops,
        lambda out: (out[3][0], out[4][0]),
        lambda: _greedy([7])[-1:],
    ),
    "prefill": (
        "_prefill", _chunk_ops,
        lambda out: (out[3][0], out[2]),
        lambda: _greedy(range(11, 11 + N_VALID))[-1:],
    ),
    "prefill_with_slots": (
        "_prefill_with_slots", lambda others: _decode_ops(others) + _chunk_ops(others),
        lambda out: (out[3][0], out[5][0], out[4][0], out[6][0]),
        lambda: _greedy([7])[-1:] + _greedy(range(11, 11 + N_VALID))[-1:],
    ),
    "verify": (
        "_verify", _verify_ops,
        lambda out: (*out[3][0], out[2][0], out[4][0]),
        lambda: _greedy([7] + _verify_ops(0)[0][0, 1:].tolist()) + [W - 1],
    ),
}


def _qkv_products(lowered) -> int:
    """``dot_general`` ops whose location lies under the ``qkv`` scope."""
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
    dots = re.findall(r"stablehlo\.dot_general.*?loc\((#loc\d+)\)", text)
    assert dots, "no dot_general with a location: the reading is blind"
    return sum("qkv/" in names.get(loc, "") for loc in dots)


def _cases():
    two_devices = pytest.mark.skipif(
        len(jax.devices("cpu")) < 2, reason="needs 2 host devices (conftest's XLA_FLAGS)")
    # no sharded joint program: the tp runner offers none (llm.multichip)
    return [(1, p) for p in PROGRAMS] + [
        pytest.param(2, p, marks=two_devices) for p in PROGRAMS if p != "prefill_with_slots"]


@pytest.mark.parametrize("tp,program", _cases())
def test_qkv_is_one_leaf_and_one_product(tp, program):
    runner = _runner(tp)
    attr, operands, row, twin = PROGRAMS[program]
    fn = getattr(runner, attr)

    # (a) one packed leaf, the given tree's bytes
    blocks = runner.params["blocks"]
    assert not {"q", "k", "v"} & set(blocks)
    d = TINY.d_model
    assert blocks["attn_qkv"]["kernel"].shape == (TINY.n_layers, d, 3 * d)
    nbytes = lambda tree: sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert nbytes(runner.params) == nbytes(_params())
    assert jax.tree_util.tree_structure(runner.given) == jax.tree_util.tree_structure(_params())

    # (b) one product a layer
    assert _qkv_products(fn.lower(runner.params, *_pools(tp), *operands(0))) == 1

    # (c) the dense twin's tokens; the same bits among other neighbours
    first, second = (
        [np.asarray(x) for x in row(fn(runner.params, *_pools(tp), *operands(others)))]
        for others in (0, 1)
    )
    want = twin()
    assert [int(t) for t in np.ravel(first[:len(want)])] == want
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)
