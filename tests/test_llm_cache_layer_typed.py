"""``llm.cache.LayerTypedPool``: blocks of two layer kinds behind one ledger.

The full layers' sub-pool is a ``KVBlockPool`` as any; the window layers'
holds, for a sequence whose next query stands at ``p``, the blocks that cover
``(p - W, p]`` and the step being written, hands every block wholly behind
that back to ITS free list, and never holds more than ``W / BS + chunk / BS +
1`` for a sequence.  The ledger calls answer as ``KVBlockPool``'s.
"""

import numpy as np
import pytest

from ray_tpu.llm.cache import KVBlockPool, LayerTypedConfig, LayerTypedPool

W, BS, CHUNK, SLOTS, TABLE = 16, 4, 8, 3, 40
LAYOUT = {"kinds": {"full": 1, "window": 2}, "window": W, "n_heads": 2, "head_dim": 8,
          "dtype": "float32"}


def _pool(num_blocks=SLOTS * TABLE + 1, **over):
    cfg = dict(num_blocks=num_blocks, block_size=BS, max_blocks_per_seq=TABLE, window=W,
               chunk=CHUNK, slots=SLOTS)
    return LayerTypedPool(LayerTypedConfig(**dict(cfg, **over)), LAYOUT)


def _prefill(pool, seq, n_tokens):
    """The engine's calls for a prompt of ``n_tokens``: chunk by chunk."""
    for start in range(0, n_tokens, CHUNK):
        pool.slide(seq, start, min(CHUNK, n_tokens - start))
        yield start


def test_the_most_window_blocks_a_sequence_holds_is_w_plus_chunk_plus_one():
    cfg = _pool().cfg
    assert cfg.window_blocks_per_seq == W // BS + CHUNK // BS + 1 == 7
    assert cfg.window_num_blocks == SLOTS * 7 + 1
    # at the published sizes: 37 blocks of 128 at a window of 4,096 and chunks of 512
    big = LayerTypedConfig(16 * 262 + 1, 128, 262, window=4096, chunk=512, slots=16)
    assert big.window_blocks_per_seq == 37 and big.window_num_blocks == 16 * 37 + 1
    # a table narrower than the window: every block of it, no more
    assert LayerTypedConfig(9, 4, 5, window=64, chunk=8, slots=1).window_blocks_per_seq == 5


def test_arrays_are_k_and_v_of_each_kind_and_a_row_is_both_tables():
    pool = _pool()
    kf, vf, kw, vw = pool.arrays
    assert kf.shape == vf.shape == (1, SLOTS * TABLE + 1, 2, BS, 8)
    assert kw.shape == vw.shape == (2, SLOTS * 7 + 1, 2, BS, 8)
    assert LayerTypedPool.n_arrays(**LAYOUT) == 4 and pool.k is kf
    assert pool.device_bytes == sum(a.nbytes for a in pool.arrays)
    assert pool.block_bytes == kf.nbytes * 2 // (SLOTS * TABLE + 1)
    assert pool.window_block_bytes == kw.nbytes * 2 // (SLOTS * 7 + 1)
    assert pool.table_row(None).shape == (2 * TABLE,) and not pool.table_row(None).any()
    pool.arrays = tuple(a + 1 for a in pool.arrays)
    assert float(pool.windowed.k[0, 0, 0, 0, 0]) == 1.0 and float(pool.full.v[0, 0, 0, 0, 0]) == 1.0


def test_a_prompt_holds_every_full_block_and_only_the_window_blocks_in_sight():
    pool = _pool()
    pool.allocate("a", 100 + BS)
    assert len(pool.blocks_of("a")) == pool.blocks_for(100 + BS) == 26
    for start in _prefill(pool, "a", 100):
        first, blocks = pool.window_blocks_of("a")
        # from the first block the chunk's first query still sees to the chunk's end
        assert first == max(start - (W - 1), 0) // BS
        assert first + len(blocks) == -(-min(start + CHUNK, 100) // BS)
        assert len(blocks) <= pool.cfg.window_blocks_per_seq
        row = pool.table_row("a")
        assert list(row[:26]) == pool.blocks_of("a")
        assert list(row[TABLE + first:TABLE + first + len(blocks)]) == blocks
        assert not row[TABLE:TABLE + first].any()  # a released entry is the trash block
    assert pool.audit()["ok"]
    counts = pool.ledger_counts()
    assert counts["seq_owned"] == counts["full_blocks_held"] == 26
    assert counts["window_blocks_held"] + counts["window_blocks_released"] == 25
    assert counts["window_blocks_held"] + counts["window_free"] == SLOTS * 7


def test_a_decode_claims_the_next_block_of_each_kind_and_slides_the_window():
    pool = _pool()
    pool.allocate("a", 30 + BS)
    list(_prefill(pool, "a", 30))
    held = []
    for n in range(31, 31 + 40):  # the decode writes position n - 1
        assert pool.grow_to("a", n)
        first, blocks = pool.window_blocks_of("a")
        assert first == max(n - 1 - (W - 1), 0) // BS and first + len(blocks) == -(-n // BS)
        assert len(pool.blocks_of("a")) >= pool.blocks_for(n)
        held.append(len(blocks))
    # W tokens behind a query touch W / BS blocks, or one more
    assert set(held[10:]) == {W // BS, W // BS + 1}
    assert pool.audit()["ok"]


def test_releases_return_to_the_window_free_list_and_are_claimed_again():
    pool = _pool()
    pool.allocate("a", 60 + BS)
    seen = set()
    for _ in _prefill(pool, "a", 60):
        seen |= set(pool.window_blocks_of("a")[1])
    # 15 logical blocks went through at most 7 physical ones and their successors
    assert pool.stats()["window_blocks_released"] == 15 - len(pool.window_blocks_of("a")[1])
    assert len(seen) <= 15 and all(1 <= b < pool.cfg.window_num_blocks for b in seen)
    assert pool.free("a") == 16 and pool.windowed.num_free_blocks == SLOTS * 7
    assert pool.audit()["ok"] and pool.ledger_counts()["window_blocks_held"] == 0


def test_admission_growth_and_pressure_are_the_full_layers():
    pool = _pool(num_blocks=21)  # 20 usable full blocks of 4 tokens
    assert pool.can_allocate(80) and not pool.can_allocate(81)
    assert not pool.can_allocate(TABLE * BS + 1)
    pool.allocate("a", 40)
    pool.allocate("b", 36)
    assert pool.num_free_blocks == 1 and pool.num_used_blocks == 19
    assert pool.utilization() == pytest.approx(19 / 20)
    with pytest.raises(MemoryError):
        pool.allocate("c", 9)
    assert pool.audit()["ok"] and sorted(pool.audit()["owners"]) == ["a", "b"]
    list(_prefill(pool, "a", 40))
    assert pool.grow_to("a", 44)          # the last free full block
    assert not pool.grow_to("a", 45)      # dry: nothing changed, the scheduler preempts
    assert len(pool.blocks_of("a")) == 11 and pool.audit()["ok"]
    assert pool.free("b") == 9            # the preempted sequence's blocks, both kinds
    assert pool.grow_to("a", 45)
    with pytest.raises(ValueError, match="already owns"):
        pool.allocate("a", 4)
    with pytest.raises(ValueError, match="shares no blocks"):
        pool.allocate("d", 4, shared=[3])
    assert pool.audit()["ok"] and pool.free("nobody") == 0


def test_a_window_claim_past_the_sub_pools_size_says_what_it_is_sized_for():
    pool = _pool(slots=1)
    for name in "ab":
        pool.allocate(name, 40)
    list(_prefill(pool, "a", 40))
    with pytest.raises(MemoryError, match="for each of 1 slots"):
        list(_prefill(pool, "b", 40))


@pytest.mark.parametrize("seed", range(6))
def test_audit_holds_after_allocate_grow_preempt_and_free_in_any_order(seed):
    rng = np.random.default_rng(seed)
    pool = _pool(num_blocks=61)
    at: dict = {}      # sequence -> (tokens computed, prompt length)
    for step in range(300):
        op = rng.integers(4)
        if op == 0 and len(at) < SLOTS:
            name, n = f"s{step}", int(rng.integers(1, 70))
            if pool.can_allocate(n + BS):
                pool.allocate(name, n + BS)
                at[name] = (0, n)
        elif op == 1 and at:  # a chunk, or a decode once the prompt is in
            name = list(at)[rng.integers(len(at))]
            done, n = at[name]
            if done < n:
                take = min(CHUNK, n - done)
                pool.slide(name, done, take)
                at[name] = (done + take, n)
            elif done + 1 <= TABLE * BS and pool.grow_to(name, done + 1):
                at[name] = (done + 1, n)
        elif op == 2 and at:  # preempted or finished: both kinds come back
            name = list(at)[rng.integers(len(at))]
            pool.free(name)
            del at[name]
        audit = pool.audit()
        assert audit["ok"], audit
        assert sorted(audit["owners"]) == sorted(at)
        for name in at:
            assert len(pool.window_blocks_of(name)[1]) <= pool.cfg.window_blocks_per_seq
        counts = pool.ledger_counts()
        assert counts["free"] + counts["seq_owned"] == 60
        assert counts["window_free"] + counts["window_blocks_held"] == SLOTS * 7
    for name in list(at):
        pool.free(name)
    assert pool.ledger_counts()["free"] == 60 and pool.windowed.num_free_blocks == SLOTS * 7


def test_release_head_is_shrink_tos_mirror():
    from ray_tpu.llm.cache import CacheConfig

    pool = KVBlockPool(CacheConfig(9, 4, 8), 1, 1, 8)
    blocks = pool.allocate("a", 20)
    assert pool.release_head("a", 2) == 2 and pool.blocks_of("a") == blocks[2:]
    assert pool.release_head("a", -1) == 0 and pool.release_head("a", 9) == 3
    assert pool.blocks_of("a") == [] and pool.num_free_blocks == 8 and pool.audit()["ok"]
    with pytest.raises(KeyError):
        pool.release_head("b", 1)
