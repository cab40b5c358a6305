"""Cross-request prefix cache (``ray_tpu.llm.prefix_cache``).

The correctness bar is hard: outputs must be TOKEN-IDENTICAL with the
cache on vs off — under greedy AND seeded sampling, composed with
speculative decoding, preemption-recompute, and mid-stream failover
resume — because prefix reuse is exact (causal attention: identical
prefixes ⇒ identical KV), never approximate.  Plus: radix-tree goldens
(insert/match/intra-block CoW split/LRU evict), the pool's refcounted
ledger with copy-on-write sharing, an eviction-under-pressure soak that
must end with clean pool AND tree audits, prefix-aware cross-request
drafting, the weight-swap flush, and the observability surface
(``llm.prefix.*`` events, ``llm_prefix_cache_*`` metrics, grafana row).
"""

import queue
import time

import numpy as np
import pytest

import jax

from ray_tpu._private import events as _events
from ray_tpu.llm import (
    CacheConfig,
    EngineConfig,
    EngineWatchdog,
    KVBlockPool,
    LLMEngine,
    NGramDrafter,
    PrefixCache,
    SamplingParams,
)
from ray_tpu.llm.prefix_cache import METRIC_NAMES
from ray_tpu.models.gptj import GPTJConfig, gptj_init

TINY = GPTJConfig(
    vocab_size=128, seq_len=64, d_model=32, n_layers=2, n_heads=2,
    rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
    fused_loss=False,
)

GREEDY = SamplingParams(max_tokens=12)
SAMPLED = SamplingParams(max_tokens=12, temperature=0.8, top_k=5, top_p=0.9,
                         seed=77)

# prompts engineered around block_size=4: a 8-token shared head, then
# per-request divergence either ON a block boundary or INSIDE a block
SHARED = [5, 6, 7, 5, 9, 2, 4, 8]
PROMPTS = [
    SHARED + [1, 3],               # boundary divergence
    SHARED + [1, 9],               # diverges INSIDE the third block (CoW)
    SHARED + [2, 2, 6, 6, 3],      # longer tail
    SHARED[:4] + [9, 9, 1, 1, 7],  # only one block shared
    [3, 1, 4, 1, 5, 9, 2, 6],      # no shared prefix at all
]


@pytest.fixture(scope="module")
def tiny_params():
    return gptj_init(jax.random.PRNGKey(0), TINY)


def _engine(params, cached, **kw):
    defaults = dict(
        max_slots=3, num_blocks=32, block_size=4, max_blocks_per_seq=12,
        prefill_chunk=8, prefix_cache=cached,
    )
    defaults.update(kw)
    return LLMEngine(TINY, params, EngineConfig(**defaults))


@pytest.fixture(scope="module")
def pair(tiny_params):
    """(cache-on, cache-off) engines — the identity-matrix workhorses."""
    return _engine(tiny_params, True), _engine(tiny_params, False)


@pytest.fixture(scope="module")
def spec_pair(tiny_params):
    return (
        _engine(tiny_params, True, spec_k=2),
        _engine(tiny_params, False, spec_k=2),
    )


def _drain(eng, req):
    deadline = time.time() + 60
    while not req.finished:
        eng.step()
        assert time.time() < deadline, "engine made no progress"
    got = []
    while True:
        try:
            kind, val = req.stream.get_nowait()[:2]  # a token carries its emit stamp third
        except queue.Empty:
            break
        if kind == "token":
            got.append(val)
        else:
            break
    return got


# ---------------------------------------------------------------------------
# pool refcounts + copy-on-write ledger


class TestPoolRefcounts:
    def _pool(self, num_blocks=9, block_size=4, bps=6):
        return KVBlockPool(
            CacheConfig(num_blocks=num_blocks, block_size=block_size,
                        max_blocks_per_seq=bps),
            n_layers=1, n_heads=1, head_dim=4,
        )

    def test_shared_allocate_refcounts_and_free_order(self):
        pool = self._pool()
        a = pool.allocate("a", 12)                     # 3 exclusive blocks
        for b in a[:2]:
            assert pool.cache_retain(b)                # tree retains 2
        assert pool.ref(a[0]) == 2
        shared = a[:2]
        b = pool.allocate("b", 12, shared=shared)      # 2 shared + 1 fresh
        assert b[:2] == shared and b[2] != a[2]
        assert pool.ref(shared[0]) == 3
        assert pool.num_used_blocks == 4               # distinct, not 6
        # free the ORIGINAL owner: shared blocks survive on b + cache refs
        freed = pool.free("a")
        assert freed == 1                              # only a's tail block
        assert pool.ref(shared[0]) == 2
        assert pool.free("b") == 1
        # now cache-only: evictable, not free
        assert pool.is_evictable(shared[0]) and pool.is_evictable(shared[1])
        assert pool.num_free_blocks == 6
        assert pool.cache_release(shared[0])           # back to the free list
        assert pool.num_free_blocks == 7
        assert pool.audit()["ok"]

    def test_allocate_validates_shared(self):
        pool = self._pool()
        a = pool.allocate("a", 8)
        with pytest.raises(ValueError, match="not cache-resident"):
            pool.allocate("b", 8, shared=[a[0]])       # owned but NOT cached
        pool.cache_retain(a[0])
        with pytest.raises(ValueError, match="exclusive"):
            pool.allocate("c", 4, shared=[a[0]])       # shared >= need
        with pytest.raises(ValueError, match="not cache-resident"):
            pool.allocate("d", 8, shared=[99])

    def test_cache_retain_rejects_free_and_double(self):
        pool = self._pool()
        assert not pool.cache_retain(3)                # free block: no resurrect
        a = pool.allocate("a", 4)
        assert pool.cache_retain(a[0])
        assert not pool.cache_retain(a[0])             # one node per block
        assert not pool.cache_release(a[0] + 1)        # not held

    def test_audit_partitions_shared_and_cached(self):
        pool = self._pool()
        a = pool.allocate("a", 8)
        pool.cache_retain(a[0])
        pool.allocate("b", 8, shared=[a[0]])
        audit = pool.audit()
        assert audit["ok"]
        assert audit["shared"] == 1 and audit["cached"] == 1
        assert audit["cached_only"] == 0 and audit["ref_errors"] == 0
        pool.free("a"), pool.free("b")
        audit = pool.audit()
        assert audit["ok"] and audit["cached_only"] == 1
        # corrupt a refcount: the audit must name it
        pool._ref[a[0]] = 5
        bad = pool.audit()
        assert not bad["ok"] and bad["ref_errors"] == 1

    def test_shrink_to_derefs_tail(self):
        pool = self._pool()
        pool.allocate("a", 20)                         # 5 blocks
        free0 = pool.num_free_blocks
        assert pool.shrink_to("a", 8) == 3
        assert pool.num_free_blocks == free0 + 3
        assert pool.audit()["ok"]


# ---------------------------------------------------------------------------
# radix tree goldens (host-only: match / insert / split / evict)


class TestRadixTree:
    def _setup(self, num_blocks=20, bs=4):
        pool = KVBlockPool(
            CacheConfig(num_blocks=num_blocks, block_size=bs,
                        max_blocks_per_seq=10),
            n_layers=1, n_heads=1, head_dim=4,
        )
        return pool, PrefixCache(pool)

    def test_empty_tree_no_match(self):
        _, cache = self._setup()
        m = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9])
        assert m.blocks == () and m.matched == 0 and m.cow_src is None

    def test_insert_then_match_full_blocks(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 9]
        blocks = pool.allocate("a", len(toks))
        assert cache.insert(toks, blocks, limit=len(toks)) == 2  # 2 full blocks
        m = cache.match(toks)
        assert list(m.blocks) == blocks[:2] and m.matched == 8
        assert pool.ref(blocks[0]) == 2                # seq + tree

    def test_match_caps_at_len_minus_one(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks = pool.allocate("a", len(toks))
        cache.insert(toks, blocks, limit=8)
        m = cache.match(toks)                          # identical prompt
        # 8 tokens cached but one must remain to prefill: 1 full block +
        # a 3-token CoW split of the second
        assert len(m.blocks) == 1 and m.matched == 7
        assert m.cow_src == blocks[1] and m.cow_tokens == 3

    def test_intra_block_split_cow(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks = pool.allocate("a", len(toks))
        cache.insert(toks, blocks, limit=8)
        m = cache.match([1, 2, 3, 4, 5, 6, 9, 9, 9, 9])
        assert list(m.blocks) == [blocks[0]]
        assert m.cow_src == blocks[1] and m.cow_tokens == 2 and m.matched == 6

    def test_cow_min_tokens_gate(self):
        pool, _ = self._setup()
        cache = PrefixCache(pool, cow_min_tokens=3)
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks = pool.allocate("a", len(toks))
        cache.insert(toks, blocks, limit=8)
        m = cache.match([1, 2, 3, 4, 5, 6, 9, 9, 9])
        assert m.cow_src is None and m.matched == 4    # 2 < min 3: no fork

    def test_insert_dedupes_existing_nodes(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5, 6, 7, 8, 1]
        a = pool.allocate("a", len(toks))
        cache.insert(toks, a, limit=8)
        b = pool.allocate("b", len(toks))              # same content, own blocks
        assert cache.insert(toks, b, limit=8) == 0     # nothing new
        assert cache.stats()["nodes"] == 2
        m = cache.match(toks)
        assert list(m.blocks) == a[:2]                 # the ORIGINAL copies

    def test_lru_eviction_leaf_first(self):
        pool, cache = self._setup()
        t1 = [1, 2, 3, 4, 5, 6, 7, 8, 0]
        t2 = [1, 2, 3, 4, 9, 9, 9, 9, 0]
        a = pool.allocate("a", len(t1))
        cache.insert(t1, a, limit=8)                   # chain: A0 -> A1
        b = pool.allocate("b", len(t2))
        cache.insert(t2, b, limit=8)                   # A0 -> B1 (shared head)
        pool.free("a"), pool.free("b")
        # everything cache-only now; t2's leaf was used more recently
        cache.match(t2)
        assert cache.evict(1) == 1                     # evicts t1's leaf (LRU)
        assert cache.match(t1).matched == 4            # head survives
        assert cache.match(t2).matched == 8
        # the shared head is NOT a leaf: unevictable until children go
        assert cache.evict(10) == 2                    # B1 leaf, then the head
        assert cache.stats()["nodes"] == 0
        assert pool.num_free_blocks == pool.cfg.num_blocks - 1
        assert pool.audit()["ok"] and cache.audit()["ok"]

    def test_evict_skips_protected_and_pinned(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5, 6, 7, 8, 0]
        a = pool.allocate("a", len(toks))
        cache.insert(toks, a, limit=8)
        # pinned: "a" still owns the blocks -> nothing evictable
        assert cache.evict(5) == 0
        pool.free("a")
        # protected: an in-flight admission is about to share the leaf
        assert cache.evict(5, protect=frozenset(a[:2])) == 0
        assert cache.evict(5) == 2

    def test_flush_releases_everything(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5, 6, 7, 8, 0]
        a = pool.allocate("a", len(toks))
        cache.insert(toks, a, limit=8)
        pool.free("a")
        assert cache.flush(reason="test") == 2
        assert cache.stats()["nodes"] == 0
        assert pool.num_free_blocks == pool.cfg.num_blocks - 1
        assert pool.audit()["ok"]

    def test_audit_catches_dangling(self):
        pool, cache = self._setup()
        toks = [1, 2, 3, 4, 5]
        a = pool.allocate("a", len(toks))
        cache.insert(toks, a, limit=4)
        assert cache.audit()["ok"]
        # simulate a dangling tree reference (release behind its back)
        pool.cache_release(a[0])
        bad = cache.audit()
        assert not bad["ok"] and bad["dangling"] == [a[0]]

    def test_paths_recency_order(self):
        pool, cache = self._setup()
        t1 = [1, 2, 3, 4, 0]
        t2 = [9, 8, 7, 6, 0]
        a = pool.allocate("a", len(t1))
        cache.insert(t1, a, limit=4)
        b = pool.allocate("b", len(t2))
        cache.insert(t2, b, limit=4)
        cache.match(t1)                                 # t1 most recent
        p = cache.paths()
        assert p[0] == [1, 2, 3, 4] and p[1] == [9, 8, 7, 6]


# ---------------------------------------------------------------------------
# the identity matrix: cache on/off × greedy/seeded × spec × preempt × resume


class TestIdentityMatrix:
    @pytest.mark.parametrize("params", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    def test_plain_engine_identity(self, pair, params):
        on, off = pair
        ref = [off.generate(p, params) for p in PROMPTS]
        cold = [on.generate(p, params) for p in PROMPTS]
        warm = [on.generate(p, params) for p in PROMPTS]  # now fully cached
        assert cold == ref and warm == ref
        assert on.stats()["prefix_cache"]["hit_tokens"] > 0
        assert on.pool.audit()["ok"] and on.prefix_cache.audit()["ok"]

    @pytest.mark.parametrize("params", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    def test_spec_decode_identity(self, spec_pair, params):
        on, off = spec_pair
        ref = [off.generate(p, params) for p in PROMPTS]
        assert [on.generate(p, params) for p in PROMPTS] == ref  # cold
        assert [on.generate(p, params) for p in PROMPTS] == ref  # warm
        assert on.pool.audit()["ok"] and on.prefix_cache.audit()["ok"]

    def test_spec_and_plain_agree_with_cache(self, pair, spec_pair):
        """Transitively: spec+cache == plain no-cache (greedy)."""
        assert [spec_pair[0].generate(p, GREEDY) for p in PROMPTS] == [
            pair[1].generate(p, GREEDY) for p in PROMPTS
        ]

    @pytest.mark.parametrize("params", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    def test_preemption_identity(self, tiny_params, params):
        """A pool too small for the whole batch: preemption-recompute and
        cache admission compose, outputs stay identical to cache-off."""
        def run(cached):
            eng = _engine(tiny_params, cached, max_slots=3, num_blocks=14,
                          max_blocks_per_seq=10, prefill_chunk=4)
            p = SamplingParams(
                max_tokens=18, temperature=params.temperature,
                top_k=params.top_k, top_p=params.top_p, seed=params.seed,
            )
            reqs = [eng.submit(pr[:8], p) for pr in PROMPTS[:3]]
            outs = [_drain(eng, r) for r in reqs]
            return eng, outs

        on, got = run(True)
        off, ref = run(False)
        assert got == ref
        assert on.pool.audit()["ok"] and on.prefix_cache.audit()["ok"]

    @pytest.mark.parametrize("params", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    def test_failover_resume_identity(self, pair, params):
        """Mid-stream failover onto a WARM replica: resume_tokens + a
        cached prefix of the replayed prompt+out sequence still continue
        token-identically at every cut."""
        on, off = pair
        full = off.generate(PROMPTS[0], params)
        on.generate(PROMPTS[0], params)                # warm the tree
        for cut in (0, 1, 5, len(full) - 1, len(full)):
            req = on.submit(PROMPTS[0], params, resume_tokens=full[:cut])
            got = _drain(on, req)
            assert full[:cut] + got == full, f"cut={cut}"
        assert on.pool.audit()["ok"] and on.prefix_cache.audit()["ok"]


# ---------------------------------------------------------------------------
# CoW fork correctness at the device level


class TestCopyOnWrite:
    def test_fork_blocks_copies_content(self, tiny_params):
        eng = _engine(tiny_params, True)
        eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], GREEDY)
        pool = eng.pool
        src = 1
        dst = pool.cfg.num_blocks - 1
        src_arr = np.zeros(eng.cfg.max_slots, np.int32)
        dst_arr = np.zeros(eng.cfg.max_slots, np.int32)
        src_arr[0], dst_arr[0] = src, dst
        pool.k, pool.v = eng.runner.fork_blocks(pool.k, pool.v, src_arr, dst_arr)
        np.testing.assert_array_equal(
            np.asarray(pool.k[:, src]), np.asarray(pool.k[:, dst])
        )
        np.testing.assert_array_equal(
            np.asarray(pool.v[:, src]), np.asarray(pool.v[:, dst])
        )

    def test_cow_admission_forks_and_matches(self, tiny_params):
        """A prompt diverging INSIDE a cached block must CoW-fork (event
        + counter) and produce the same output as a cold engine."""
        eng = _engine(tiny_params, True)
        off = _engine(tiny_params, False)
        base = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        div = [1, 2, 3, 4, 5, 9, 9, 9, 9]     # diverges at block-1 offset 1
        eng.generate(base, GREEDY)
        forks0 = eng.prefix_cache.stats()["cow_forks"]
        assert eng.generate(div, GREEDY) == off.generate(div, GREEDY)
        assert eng.prefix_cache.stats()["cow_forks"] == forks0 + 1
        assert eng.pool.audit()["ok"]


# ---------------------------------------------------------------------------
# eviction soak + watchdog composition


class TestEvictionSoak:
    def test_soak_under_kv_pressure_ends_clean(self, tiny_params):
        """Many distinct prompts from a few shared families through a pool
        far too small to retain them all: admission evicts LRU cached
        blocks, preemption still works, and the final pool AND tree
        audits are clean (the watchdog's composed view included)."""
        eng = _engine(tiny_params, True, max_slots=2, num_blocks=16,
                      max_blocks_per_seq=10, prefill_chunk=4)
        rng = np.random.RandomState(0)
        fams = [list(rng.randint(0, TINY.vocab_size, 8)) for _ in range(3)]
        reqs = []
        for i in range(24):
            fam = fams[i % len(fams)]
            prompt = fam + list(rng.randint(0, TINY.vocab_size, 4))
            reqs.append(eng.submit(prompt, SamplingParams(max_tokens=6)))
            eng.step()
        for r in reqs:
            _drain(eng, r)
        s = eng.prefix_cache.stats()
        assert s["hit_tokens"] > 0, "families never hit the cache"
        assert s["evicted_blocks"] > 0, "the pool never saw pressure"
        assert eng.pool.audit()["ok"], eng.pool.audit()
        assert eng.prefix_cache.audit()["ok"], eng.prefix_cache.audit()
        wd = EngineWatchdog(eng)
        info = wd.check_once()
        assert info["audit"]["ok"]
        assert info["audit"]["prefix_cache"]["ok"]

    def test_watchdog_flags_dangling_tree_reference(self, tiny_params):
        eng = _engine(tiny_params, True)
        eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], GREEDY)
        wd = EngineWatchdog(eng)
        assert wd.check_once()["audit"]["ok"]
        blk = next(iter(eng.prefix_cache._by_block))
        eng.pool.cache_release(blk)                    # corrupt: node remains
        info = wd.check_once()
        assert not info["audit"]["ok"]
        assert info["audit"]["prefix_cache"]["dangling"] == [blk]
        assert wd.leak_count == 1


# ---------------------------------------------------------------------------
# prefix-aware drafting, weight-swap flush, observability surface


class TestPrefixAwareDrafting:
    def test_corpus_match_drafts_from_shared_paths(self):
        d = NGramDrafter(k=3, max_ngram=3)
        # the continuation of (7, 8) lives ONLY in the shared corpus
        d.corpus = lambda: [[1, 2, 7, 8, 40, 41, 42, 43]]
        out = d.propose([[9, 9, 9, 7, 8]])
        assert out.tolist() == [[40, 41, 42]]
        assert d.last_matched.tolist() == [True]

    def test_local_match_still_wins(self):
        d = NGramDrafter(k=2, max_ngram=3)
        d.corpus = lambda: [[5, 6, 99, 99]]
        out = d.propose([[5, 6, 1, 2, 5, 6]])          # local bigram match
        assert out.tolist() == [[1, 2]]

    def test_no_corpus_single_token_is_noise(self):
        d = NGramDrafter(k=2, max_ngram=3)
        d.corpus = lambda: [[7, 40, 41]]               # only n=1 would match
        out = d.propose([[1, 2, 3, 7]])
        assert d.last_matched.tolist() == [False]
        assert out.tolist() == [[7, 7]]                # repeat-last fallback

    def test_engine_wires_corpus(self, tiny_params):
        eng = _engine(tiny_params, True, spec_k=2)
        assert eng._drafter.corpus is not None
        eng2 = _engine(tiny_params, False, spec_k=2)
        assert eng2._drafter.corpus is None


class TestWeightSwapFlush:
    def test_update_weights_flushes_tree(self, tiny_params):
        eng = _engine(tiny_params, True)
        off = _engine(tiny_params, False)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        ref = off.generate(prompt, GREEDY)
        eng.generate(prompt, GREEDY)
        assert eng.prefix_cache.stats()["nodes"] > 0
        eng.update_weights(tiny_params)                # same params, new version
        assert eng.prefix_cache.stats()["nodes"] == 0  # stale KV dropped
        assert eng.pool.num_free_blocks == eng.pool.cfg.num_blocks - 1
        assert eng.generate(prompt, GREEDY) == ref     # recomputed, identical
        assert eng.pool.audit()["ok"]


    def test_mid_prefill_weight_swap_never_reinserts_stale_kv(self, tiny_params):
        """The epoch guard: a request whose chunked prefill STRADDLES an
        update_weights flush computed (some of) its KV under the old
        parameters — its later prefill chunks must not re-register blocks
        into the flushed tree, or a follow-up request would seed stale
        KV and diverge from the cache-off engine."""
        v2 = gptj_init(jax.random.PRNGKey(9), TINY)
        eng = _engine(tiny_params, True, prefill_chunk=4)
        prompt = list(np.random.RandomState(5).randint(0, TINY.vocab_size, 12))
        req = eng.submit(prompt, GREEDY)
        eng.step()                                     # admit + first chunk only
        assert req.prefill_pos < len(prompt)
        eng.update_weights(v2)                         # flush mid-prefill
        _drain(eng, req)                               # finishes under v2
        # the straddling request's blocks never re-entered the tree
        assert eng.prefix_cache.stats()["nodes"] == 0
        # a fresh request prefills under v2 throughout and must match a
        # pure-v2 engine exactly (and MAY now populate the tree)
        ref = _engine(v2, False).generate(prompt, GREEDY)
        assert eng.generate(prompt, GREEDY) == ref
        assert eng.prefix_cache.stats()["nodes"] > 0
        assert eng.generate(prompt, GREEDY) == ref     # warm, still v2-exact
        assert eng.pool.audit()["ok"] and eng.prefix_cache.audit()["ok"]


class TestObservability:
    def test_prefix_events_and_stats(self, tiny_params):
        _events.clear()
        eng = _engine(tiny_params, True)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        eng.generate(prompt, GREEDY)
        eng.generate(prompt, GREEDY)
        types = [e["type"] for e in _events.snapshot()]
        assert "llm.prefix.insert" in types
        assert "llm.prefix.hit" in types
        hit = next(
            e for e in _events.snapshot() if e["type"] == "llm.prefix.hit"
        )
        assert hit["matched_tokens"] > 0 and hit["engine_req"]
        admit = [e for e in _events.snapshot() if e["type"] == "llm.admit"]
        assert admit[-1]["cached_tokens"] == hit["matched_tokens"]
        s = eng.stats()
        assert s["prefix_cache"]["hit_rate"] > 0
        assert s["prefill_tokens_computed"] > 0

    def test_grafana_row_matches_metric_names(self):
        """The dashboard's prefix row must not drift from the metric
        family the cache actually exports (prefix_cache.METRIC_NAMES)."""
        from ray_tpu.util.grafana import dashboard_json

        doc = str(dashboard_json())
        for name in METRIC_NAMES:
            assert name in doc, f"grafana row missing {name}"

    def test_observability_doc_names_the_family(self):
        import pathlib

        doc = pathlib.Path(__file__).parent.parent / "OBSERVABILITY.md"
        text = doc.read_text()
        assert "llm.prefix.*" in text
        for name in METRIC_NAMES:
            assert name in text, f"OBSERVABILITY.md missing {name}"

    def test_serve_autoscaling_metrics_include_hit_rate(self, tiny_params):
        from ray_tpu.serve.llm import LLMDeployment

        dep = LLMDeployment.__new__(LLMDeployment)
        dep._engine = _engine(tiny_params, True)
        m = dep.autoscaling_metrics()
        assert "prefix_hit_rate" in m
        dep._engine = _engine(tiny_params, False)
        assert "prefix_hit_rate" not in dep.autoscaling_metrics()
