"""Token-identity matrix for the tensor-parallel engine (llm.multichip).

The multi-chip contract is behavioral, not numerical: an
``EngineConfig(tp=N)`` engine must emit EXACTLY the token stream the
single-chip engine emits — greedy and seeded sampling, speculative
decode on and off, through recompute preemption, failover
``resume_tokens`` and the prefix cache — while the host-side machinery
(ledger audit, watchdog, HBM gauges) keeps its invariants over the
sharded pool.  Per-head attention is bitwise identical under the head
split; only the two row-parallel psums reorder floating-point
reductions (~1 ulp/layer), which greedy argmax and fixed-seed sampling
absorb — these tests pin that.

Runs on jax host-platform CPU devices (conftest forces 8 via
``XLA_FLAGS=--xla_force_host_platform_device_count``), tp in {2, 4}
against the tp=1 reference.  Engines are lru_cached module-wide: each
(tp, spec, prefix) point jits once and every test reads it.
"""

import functools

import numpy as np
import pytest

from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.llm.scheduler import SamplingParams
from ray_tpu.models.gptj import GPTJConfig, gptj_init


def _multi_device_cpu() -> bool:
    """Same capability probe as test_spmd_contracts: the devices exist
    only if the conftest's XLA_FLAGS landed before jax initialized."""
    import jax

    return len(jax.devices("cpu")) >= 4


pytestmark = pytest.mark.skipif(
    not _multi_device_cpu(),
    reason="needs a >=4-device CPU mesh "
    "(XLA_FLAGS=--xla_force_host_platform_device_count, set by conftest)",
)

# tp=4-divisible geometry: 4 heads x head_dim 16, d_ff 256
TINY = GPTJConfig(
    vocab_size=128, seq_len=64, d_model=64, n_layers=2, n_heads=4,
    rotary_dim=8, dtype="float32", remat=False, attn_impl="xla",
    fused_loss=False,
)

GREEDY = SamplingParams(max_tokens=8, temperature=0.0)
SEEDED = SamplingParams(max_tokens=8, temperature=0.8, seed=42)
PROMPT = [1, 2, 3, 4, 5]


@functools.lru_cache(maxsize=1)
def _params():
    import jax

    return gptj_init(jax.random.PRNGKey(0), TINY)


def _engine(tp=1, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_blocks_per_seq", 12)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(TINY, _params(), EngineConfig(tp=tp, **kw))


def _drive(eng, reqs, max_steps=500):
    for _ in range(max_steps):
        if all(r.finished for r in reqs):
            return [list(r.out) for r in reqs]
        eng.step()
    raise AssertionError("engine did not finish")


@functools.lru_cache(maxsize=None)
def _matrix(tp: int, spec_k: int, prefix_cache: bool):
    """The standard request set (greedy + two seeded temperatures) on a
    fresh engine; returns (outputs, engine) — the engine stays alive for
    audit/ledger tests, the outputs are the identity fixture."""
    eng = _engine(tp=tp, spec_k=spec_k, prefix_cache=prefix_cache)
    reqs = [
        eng.submit(list(PROMPT), GREEDY),
        eng.submit([7, 8, 9], SEEDED),
        eng.submit(
            list(range(1, 13)),
            SamplingParams(max_tokens=6, temperature=0.6, seed=7, top_k=20),
        ),
    ]
    out = _drive(eng, reqs)
    return tuple(map(tuple, out)), eng


# --------------------------------------------------------------- identity


@pytest.mark.parametrize("tp", [2, 4])
def test_greedy_and_seeded_token_identity(tp):
    ref, _ = _matrix(1, 0, True)
    got, _ = _matrix(tp, 0, True)
    assert got == ref


# tier-1 budget (ISSUE 20): 11.0s measured at tp=2 — rides slow; the
# multichip-engine-smoke CI job runs this file in full on every push and
# single-chip spec identity stays gated by tests/test_llm_spec.py
@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_spec_decode_token_identity(tp):
    """Speculative decoding under tp: drafting is host-side, the sharded
    verify step must accept/correct exactly like single-chip."""
    ref, ref_eng = _matrix(1, 2, True)
    got, eng = _matrix(tp, 2, True)
    assert got == ref
    assert eng.stats()["spec_proposed"] > 0


# tier-1 budget (ISSUE 20): 6.7s measured across params — rides slow; the
# multichip-engine-smoke CI job runs this file in full, single-chip prefix
# identity stays gated by tests/test_llm_prefix.py, and the warm-path
# identity below stays in tier-1
@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_prefix_cache_off_token_identity(tp):
    ref, _ = _matrix(1, 0, False)
    got, _ = _matrix(tp, 0, False)
    assert got == ref


@pytest.mark.parametrize("tp", [2, 4])
def test_prefix_cache_warm_path_identity(tp):
    """A warm request sharing PROMPT as its prefix seeds from cached
    blocks (sharded CoW fork underneath) — still token-identical."""
    warm = SamplingParams(max_tokens=6, temperature=0.0)
    _, ref_eng = _matrix(1, 0, True)
    _, eng = _matrix(tp, 0, True)  # same traffic -> same cache state
    prompt = list(PROMPT) + [21, 22]
    ref = ref_eng.generate(prompt, warm)
    hits_before = eng.prefix_cache.stats()["hit_tokens"]
    got = eng.generate(prompt, warm)
    assert got == ref
    assert eng.prefix_cache.stats()["hit_tokens"] > hits_before


# tier-1 budget (ISSUE 20): 8.8s measured across params — rides slow; the
# multichip-engine-smoke CI job runs this file in full and single-chip
# preemption identity stays gated by tests/test_llm_spec.py
@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_preemption_recompute_identity(tp):
    """A pool too small for all completions forces recompute preemption;
    the sharded engine preempts and recovers to the same tokens."""

    def run(tp_):
        eng = _engine(
            tp=tp_, max_slots=3, num_blocks=13, block_size=4,
            max_blocks_per_seq=10,
        )
        prompts = [
            list(np.random.RandomState(s).randint(0, TINY.vocab_size, 8))
            for s in (5, 6, 7)
        ]
        reqs = [eng.submit(p, SamplingParams(max_tokens=16)) for p in prompts]
        out = _drive(eng, reqs)
        assert eng.stats()["preemptions"] > 0, "pool sized to force preemption"
        assert eng.pool.audit()["ok"]
        return out

    assert run(tp) == run(1)


@pytest.mark.parametrize("tp", [2, 4])
def test_failover_resume_tokens_identity(tp):
    """Mid-stream failover onto a tp replica: resuming from a tp=1
    replica's delivered prefix reproduces the unkilled run exactly."""
    (full, _seeded, _), _ = _matrix(1, 0, True)
    _, eng = _matrix(tp, 0, True)
    full = list(full)
    req = eng.submit(list(PROMPT), GREEDY, resume_tokens=full[:3])
    out = _drive(eng, [req])[0]
    assert out == full


# ----------------------------------------------------- sharded invariants


@pytest.mark.parametrize("tp", [2, 4])
def test_audit_and_watchdog_pass_sharded(tp):
    from ray_tpu.llm.watchdog import EngineWatchdog

    _, eng = _matrix(tp, 0, True)
    assert eng.pool.audit()["ok"]
    info = EngineWatchdog(eng, stall_deadline_s=30.0).check_once()
    assert info["audit"]["ok"]
    assert not info["stalled"]
    # the pool a step hands back compares equal to the one it was given
    # (ShardedKVBlockPool spells its placement the way jax spells step
    # outputs), so no jit site ever grows a second cache entry
    assert eng.runner.prof.retraces == 0
    assert all(s["cache_size"] == 1 for s in eng.runner.prof.stats().values())
    # the report lowers every step again from its first call's operands —
    # sharded ones included — and that is not a retrace either
    kernels = eng.device_report()["attention"]["mosaic_kernels"]
    assert {"decode", "prefill"} <= set(kernels) and not any(kernels.values())
    eng.generate([3, 1, 4, 1, 5], SamplingParams(max_tokens=4))
    assert eng.runner.prof.retraces == 0
    # the partials are gathered and added in device order, never psum'd:
    # on TPU chips a bf16 all-reduce rounds a row by its place in the
    # buffer and a prefix hit then decodes other tokens than the cold run
    # (PERF.md, PR 21) — which no CPU run can show, so pin the program
    fn, args, static = eng.runner._first_operands["prefill"]
    text = fn.lower(*args, **static).as_text()
    assert "all_gather" in text and "all_reduce" not in text


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_stats_carry_tp_and_the_reductions_ledger(tp):
    """``stats()`` names the mesh size and, under tp, what ``_tp_sum``
    noted while each step program was traced (one reduction a GPT-J
    layer, the other devices' float32 partials received), times the
    launches: the ledger is read off the traced code, not computed."""
    _, eng = _matrix(tp, 0, True)
    stats = eng.stats()
    assert stats["tp"] == tp
    if tp == 1:
        assert "tp_sum" not in stats
        return
    row = TINY.d_model * 4 * (tp - 1) * TINY.n_layers
    led = stats["tp_sum"]
    assert led["per_step"] == {
        "prefill": {"calls": TINY.n_layers, "bytes": 8 * row},
        "decode": {"calls": TINY.n_layers, "bytes": 3 * row},
    }
    launched = eng.runner.prof.stats()
    assert led["calls"] == TINY.n_layers * (
        launched["prefill"]["calls"] + launched["decode"]["calls"]
    )
    assert led["bytes"] == row * (
        8 * launched["prefill"]["calls"] + 3 * launched["decode"]["calls"]
    )


@pytest.mark.parametrize("site", ["decode", "prefill", "verify"])
@pytest.mark.parametrize("arch,sums_a_layer", [("gptj", 1), ("gpt", 2)])
def test_reductions_a_layer_follow_the_inherited_layer(arch, sums_a_layer, site):
    """The one ``_layer`` under the mesh, each program lowered (traced, not
    run): GPT-J's parallel residual sums attention and MLP partials ONCE a
    layer, GPT's sequential residual twice, and each call receives the
    other device's rows."""
    import jax

    from ray_tpu.llm.multichip import TensorParallelPagedModelRunner
    from ray_tpu.models.gpt import GPTConfig, gpt_init

    cfg, init = TINY, gptj_init
    if arch == "gpt":
        cfg, init = GPTConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                              seq_len=64, dtype="float32"), gpt_init
    runner = TensorParallelPagedModelRunner(
        cfg, init(jax.random.PRNGKey(0), cfg), 4, "xla", tp=2)

    def a(*shape, dtype="int32"):
        return jax.ShapeDtypeStruct(shape, dtype)

    S, W, T = 3, 3, 12  # slots, window, table width; a chunk of 8
    pool = a(cfg.n_layers, 16, cfg.n_heads, 4, cfg.head_dim, dtype="float32")
    rows, operands = {
        "decode": (S, (a(3, S), a(1), a(S, 4), a(S, T), a(S, 5))),
        "prefill": (8, (a(8), a(), a(), a(T), a(5))),
        "verify": (S * W, (a(S, W), a(S), a(S, T), a(S, dtype="float32"), a(S),
                           a(S, dtype="float32"), a(S, dtype="uint32"), a(S))),
    }[site]
    getattr(runner, "_" + site).lower(runner.params, pool, pool, *operands)
    calls = cfg.n_layers * sums_a_layer
    assert runner.tp_sum_stats()["per_step"] == {
        site: {"calls": calls, "bytes": calls * rows * cfg.d_model * 4}}


@pytest.mark.parametrize("name", [
    "_layer", "_decode_impl", "_verify_impl", "_prefill_impl",
    "_prefill_with_slots_impl", "prefill_chunk",
    "_tp_layer", "_tp_layers", "_decode_shard", "_verify_shard", "_prefill_shard",
])
def test_tp_runner_owns_no_step_body(name):
    """The step programs are written once: the mesh wraps the one-chip
    runner's bodies, and the names of the second copies are gone."""
    from ray_tpu.llm.model_runner import PagedModelRunner
    from ray_tpu.llm.multichip import TensorParallelPagedModelRunner as TP

    assert name not in vars(TP)
    assert getattr(TP, name, None) is getattr(PagedModelRunner, name, None)


@pytest.mark.parametrize("tp", [2, 4])
def test_per_device_hbm_ledger(tp):
    """Per-device attribution: the pool splits exactly 1/tp per device,
    the kv partition scales with local block bytes, params per device
    exceed the even split (replicated leaves are a full copy each), and
    the top-level (pool-wide) numbers match the tp=1 engine's."""
    _, ref_eng = _matrix(1, 0, True)
    _, eng = _matrix(tp, 0, True)
    led = eng.hbm_ledger()
    ref = ref_eng.hbm_ledger()
    assert led["pool_bytes"] == ref["pool_bytes"]
    assert led["params_bytes"] == ref["params_bytes"]
    per = led["per_device"]
    assert len(per) == tp
    assert sum(row["pool_bytes"] for row in per.values()) == led["pool_bytes"]
    for row in per.values():
        assert row["pool_bytes"] == led["pool_bytes"] // tp
        assert row["params_bytes"] > led["params_bytes"] // tp
        # the local kv partition covers the usable local blocks
        bb_local = row["pool_bytes"] // eng.pool.cfg.num_blocks
        usable = (eng.pool.cfg.num_blocks - 1) * bb_local
        assert row["seq_bytes"] + row["cache_bytes"] + row["free_bytes"] == usable
    assert "per_device" not in ref


def test_hbm_gauges_carry_device_tag():
    """tp>1 publishes the same gauge NAMES split by a device tag (RL012:
    no new names); the untagged series stays pool-wide."""
    from ray_tpu.util import metrics as um

    _, eng = _matrix(2, 0, True)
    eng._publish_gauges()
    led = eng.hbm_ledger()
    data = {
        m.name: m._snapshot()["data"]
        for m in um._registry
        if m.name == "llm_hbm_kv_pool_bytes"
    }["llm_hbm_kv_pool_bytes"]
    assert data.get("") == led["pool_bytes"]  # untagged = pool-wide
    tagged = {k: v for k, v in data.items() if "device" in k}
    assert len(tagged) >= 2
    assert sum(v for v in tagged.values() if v == led["pool_bytes"] // 2) \
        == led["pool_bytes"]


# tier-1 budget (ISSUE 20): 9.6s measured across params — rides slow; the
# multichip-engine-smoke CI job runs this file in full and the swap contract
# stays gated by tests/test_llm_weight_swap.py + the rlhf hot-swap tests
@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 4])
def test_update_weights_sharded_hot_swap(tp):
    """update_weights routes through the tp runner's prepare_params:
    the swap lands sharded and the engine continues token-identical to
    a single-chip engine born with the new weights."""
    import jax

    eng = _engine(tp=tp)
    eng.warmup()
    new = gptj_init(jax.random.PRNGKey(1), TINY)
    assert eng.update_weights(new) == 1
    ref_eng = LLMEngine(
        TINY, new,
        EngineConfig(max_slots=3, num_blocks=32, block_size=4,
                     max_blocks_per_seq=12, prefill_chunk=8),
    )
    want = ref_eng.generate(list(PROMPT), GREEDY)
    assert eng.generate(list(PROMPT), GREEDY) == want


@pytest.mark.parametrize("arch", ["gptj", "gpt"])
@pytest.mark.parametrize("tp", [2, 4])
def test_packed_qkv_shard_is_its_head_groups_q_k_v(tp, arch):
    """``attn_qkv``'s shard on device ``i`` is ``[Q_i | K_i | V_i]``, the
    columns of head group ``i``: GPT-J's three given kernels, or the thirds
    of GPT's fused ``[Q | K | V]`` kernel and bias, whether the runner was
    handed host leaves or shards that ``param_shardings`` placed."""
    import jax
    import numpy as np

    from ray_tpu.llm.multichip import TensorParallelPagedModelRunner, param_shardings
    from ray_tpu.models.gpt import GPTConfig, gpt_init

    cfg, init = TINY, gptj_init
    if arch == "gpt":
        cfg, init = GPTConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                              seq_len=64, dtype="float32"), gpt_init
    given = init(jax.random.PRNGKey(3), cfg)
    blocks = jax.device_get(given["blocks"])
    if arch == "gptj":
        parts = {"kernel": [blocks[m]["kernel"] for m in "qkv"]}
        placed = jax.device_put(given, param_shardings(given, tp))
    else:
        parts = {slot: np.split(x, 3, axis=-1) for slot, x in blocks["attn_qkv"].items()}
        placed = given  # the fused leaf has no column placement to be born in
    w = cfg.d_model // tp
    for tree in (jax.device_get(given), placed):
        runner = TensorParallelPagedModelRunner(cfg, tree, 4, "xla", tp=tp)
        for slot, (q, k, v) in parts.items():
            leaf = runner.params["blocks"]["attn_qkv"][slot]
            assert leaf.shape == q.shape[:-1] + (3 * cfg.d_model,)
            shards = sorted(leaf.addressable_shards, key=lambda sh: sh.index[-1].start)
            assert len(shards) == tp
            for i, shard in enumerate(shards):
                cols = slice(i * w, (i + 1) * w)
                np.testing.assert_array_equal(
                    np.asarray(shard.data),
                    np.concatenate([q[..., cols], k[..., cols], v[..., cols]], axis=-1),
                )


def test_divisibility_validation():
    from ray_tpu.llm.cache import CacheConfig
    from ray_tpu.llm.multichip import (
        ShardedKVBlockPool,
        TensorParallelPagedModelRunner,
    )

    with pytest.raises(ValueError, match="not divisible"):
        ShardedKVBlockPool(
            CacheConfig(num_blocks=8, block_size=4, max_blocks_per_seq=4),
            n_layers=2, n_heads=4, head_dim=16, tp=3,
        )
    with pytest.raises(ValueError, match="not divisible"):
        TensorParallelPagedModelRunner(TINY, _params(), 4, tp=3)
