"""ray_tpu.rlhf: disaggregated async RL-on-LLM.

Unit pins (no cluster): staleness gate golden ratios + version-K drop
behavior, importance-ratio goldens, GRPO advantages, staging buffer.
Integration (cluster fixtures): chunked weight publication roundtrip,
engine hot-swap without draining, version stamping, the rollout
trajectory contract, the serve-hosted push path sharing the sync code
path, and (slow) the end-to-end async loop: reward improves while
rollout and learner provably overlap.
"""

import math
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.llm.engine import EngineConfig, LLMEngine  # noqa: E402
from ray_tpu.llm.scheduler import SamplingParams  # noqa: E402
from ray_tpu.models.gpt import GPTConfig, gpt_init  # noqa: E402
from ray_tpu.rlhf import (  # noqa: E402
    Algorithm,
    RLHFConfig,
    RolloutWorker,
    TrajectoryBuffer,
    apply_weight_update,
    fetch_params,
    group_advantages,
    importance_ratios,
    publish_weights,
    staleness_weights,
)

TINY = GPTConfig(
    vocab_size=32, seq_len=64, d_model=32, n_layers=1, n_heads=2,
    remat=False, fused_loss=False, dtype="float32",
)
ENG = EngineConfig(
    max_slots=4, num_blocks=64, block_size=4, max_blocks_per_seq=8,
    prefill_chunk=8,
)


@pytest.fixture(scope="module")
def tiny_params():
    return gpt_init(jax.random.PRNGKey(0), TINY)


# ---------------------------------------------------------------------------
# unit: staleness gate + importance correction (golden-pinned)
# ---------------------------------------------------------------------------


class TestStalenessGate:
    def test_drop_mode_version_k_boundary(self):
        """The gate's contract: age <= K admits at full weight, age K+1
        drops — pinned exactly at the boundary."""
        w = staleness_weights([0, 1, 3, 4, 5, 9], max_staleness=4, mode="drop")
        np.testing.assert_array_equal(w, [1.0, 1.0, 1.0, 1.0, 0.0, 0.0])

    def test_downweight_mode_goldens(self):
        """Past the gate every halflife of extra age halves the weight:
        age K -> 1, K+1 -> 0.5, K+2 -> 0.25 (halflife=1)."""
        w = staleness_weights([0, 2, 3, 4, 6], max_staleness=2,
                              mode="downweight", halflife=1.0)
        np.testing.assert_allclose(w, [1.0, 1.0, 0.5, 0.25, 0.0625], atol=1e-7)

    def test_downweight_halflife_scales(self):
        w = staleness_weights([4], max_staleness=0, mode="downweight",
                              halflife=2.0)
        np.testing.assert_allclose(w, [0.25], atol=1e-7)

    def test_negative_age_counts_as_fresh(self):
        # an engine that applied a push before the learner's bookkeeping
        # stamps a FUTURE version; that is freshness, not staleness
        assert staleness_weights([-3], 0, "drop")[0] == 1.0

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError):
            staleness_weights([1], 1, mode="decay")


class TestImportanceCorrection:
    def test_ratio_goldens(self):
        """ratio = exp(cur - behavior), hand-computed."""
        behavior = np.log([0.5, 0.25, 0.1])
        current = np.log([0.25, 0.25, 0.2])
        r = importance_ratios(behavior, current)
        np.testing.assert_allclose(r, [0.5, 1.0, 2.0], atol=1e-6)

    def test_clip_golden(self):
        r = importance_ratios(
            np.log([0.5, 0.1, 0.4]), np.log([0.25, 0.9, 0.4]), clip=0.2
        )
        np.testing.assert_allclose(r, [0.8, 1.2, 1.0], atol=1e-6)

    def test_group_advantages_standardize(self):
        adv = group_advantages([1.0, 2.0, 3.0])
        np.testing.assert_allclose(adv.mean(), 0.0, atol=1e-6)
        np.testing.assert_allclose(adv.std(), 1.0, atol=1e-5)

    def test_group_advantages_zero_variance_is_zero(self):
        # no contrast, no gradient: a constant-reward batch must not
        # produce NaNs or a fake learning signal
        np.testing.assert_array_equal(group_advantages([0.3, 0.3, 0.3]),
                                      [0.0, 0.0, 0.0])


class TestTrajectoryBuffer:
    def test_fifo_and_overflow_drops_oldest(self):
        buf = TrajectoryBuffer(capacity=3)
        buf.add([{"i": k} for k in range(5)])
        assert [t["i"] for t in buf.take(3, timeout=1)] == [2, 3, 4]
        assert buf.stats()["dropped_overflow"] == 2

    def test_take_blocks_until_staged(self):
        buf = TrajectoryBuffer(capacity=8)
        got = []

        def consumer():
            got.extend(buf.take(2, timeout=5))

        th = threading.Thread(target=consumer)
        th.start()
        time.sleep(0.05)
        buf.add([{"i": 1}, {"i": 2}])
        th.join(timeout=5)
        assert len(got) == 2

    def test_take_timeout_returns_partial(self):
        buf = TrajectoryBuffer(capacity=8)
        buf.add([{"i": 1}])
        assert len(buf.take(4, timeout=0.05)) == 1


class TestLoss:
    def _batch(self, **over):
        B, T, O = 2, 8, 4
        base = dict(
            tokens=np.tile(np.arange(T, dtype=np.int32), (B, 1)),
            prompt_len=np.full(B, 3, np.int32),
            out_tokens=np.tile(np.arange(3, 3 + O, dtype=np.int32), (B, 1)),
            out_len=np.full(B, O, np.int32),
            behavior_logp=np.full((B, O), -2.0, np.float32),
            token_mask=np.ones((B, O), np.float32),
            advantage=np.asarray([1.0, -1.0], np.float32),
            weight=np.ones(B, np.float32),
            temperature=np.ones(B, np.float32),
            top_k=np.zeros(B, np.int32),
            top_p=np.ones(B, np.float32),
        )
        base.update(over)
        return {k: jnp.asarray(v) for k, v in base.items()}

    def test_token_mask_excludes_unknown_behavior(self, tiny_params):
        """A masked token must contribute NOTHING: garbage behavior_logp
        under mask 0 leaves the loss bit-identical (the failover-resume
        NaN contract)."""
        from ray_tpu.rlhf.learner import GPTPolicyModule, rlhf_loss

        module = GPTPolicyModule(TINY)
        loss_fn = rlhf_loss(clip_param=0.2)
        mask = np.ones((2, 4), np.float32)
        mask[0, 1] = 0.0
        blp = np.full((2, 4), -2.0, np.float32)
        l1, m1 = loss_fn(module, tiny_params,
                         self._batch(token_mask=mask, behavior_logp=blp))
        blp2 = blp.copy()
        blp2[0, 1] = 123.0  # garbage where masked
        l2, m2 = loss_fn(module, tiny_params,
                         self._batch(token_mask=mask, behavior_logp=blp2))
        assert float(l1) == float(l2)
        assert float(m1["kl"]) == float(m2["kl"])

    def test_kl_finite_when_current_filter_masks_behavior_token(
        self, tiny_params
    ):
        """top_k=1 under the CURRENT policy masks most behavior tokens
        (~-1e30 scores): ratio goes to 0 (clipped, fine) and the KL term
        must stay clamped-finite instead of exploding to ~1e30."""
        from ray_tpu.rlhf.learner import GPTPolicyModule, rlhf_loss

        module = GPTPolicyModule(TINY)
        loss_fn = rlhf_loss(clip_param=0.2, kl_coeff=0.01)
        loss, metrics = loss_fn(
            module, tiny_params, self._batch(top_k=np.ones(2, np.int32))
        )
        assert np.isfinite(float(loss))
        assert abs(float(metrics["kl"])) <= 20.0 + 1e-6


# ---------------------------------------------------------------------------
# engine hot-swap (no cluster)
# ---------------------------------------------------------------------------


class TestEngineHotSwap:
    def test_swap_without_draining_in_flight(self, tiny_params):
        """A weight push lands mid-generation: the in-flight request
        keeps its slot, finishes under the new weights, and keeps its
        submit-time version stamp; later submits stamp the new version."""
        eng = LLMEngine(TINY, tiny_params, ENG)
        req = eng.submit([1, 2, 3], SamplingParams(max_tokens=12,
                                                   temperature=1.0, seed=1))
        for _ in range(4):
            eng.step()
        mid = len(req.out)
        assert 0 < mid < 12 and req.weights_version == 0
        v = eng.update_weights(gpt_init(jax.random.PRNGKey(9), TINY), 1)
        assert v == 1
        while not req.finished:
            eng.step()
        assert len(req.out) == 12 and req.finish_reason == "length"
        assert req.weights_version == 0  # stamped at submit
        # every token has a captured behavior logprob across the swap
        assert not any(math.isnan(x) for x in req.out_logprobs)
        req2 = eng.submit([1], SamplingParams(max_tokens=2))
        assert req2.weights_version == 1

    def test_swap_changes_future_tokens_deterministically(self, tiny_params):
        """Same request params under v0 and under pushed v1 weights give
        different outputs, and v1 output equals a fresh v1 engine's (the
        swap installs exactly the pushed params)."""
        other = gpt_init(jax.random.PRNGKey(9), TINY)
        sp = SamplingParams(max_tokens=8, temperature=1.0, seed=4)

        def gen(engine):
            r = engine.submit([2, 3, 4], sp)
            while not r.finished:
                engine.step()
            return r.out

        e0 = LLMEngine(TINY, tiny_params, ENG)
        base = gen(e0)
        e0.update_weights(other, 1)
        swapped = gen(e0)
        fresh = gen(LLMEngine(TINY, other, ENG))
        assert swapped == fresh
        assert swapped != base  # different weights actually took effect

    def test_structure_and_shape_mismatch_rejected(self, tiny_params):
        eng = LLMEngine(TINY, tiny_params, ENG)
        with pytest.raises(ValueError, match="structure"):
            eng.update_weights({"not": np.zeros(2)}, 1)
        bigger = gpt_init(
            jax.random.PRNGKey(1),
            GPTConfig(vocab_size=32, seq_len=64, d_model=64, n_layers=1,
                      n_heads=2, remat=False, fused_loss=False,
                      dtype="float32"),
        )
        with pytest.raises(ValueError, match="leaf mismatch"):
            eng.update_weights(bigger, 1)

    def test_version_never_goes_backwards(self, tiny_params):
        eng = LLMEngine(TINY, tiny_params, ENG)
        eng.update_weights(tiny_params, 3)
        with pytest.raises(ValueError, match="backwards"):
            eng.update_weights(tiny_params, 2)
        # idempotent re-delivery of the same version is fine
        assert eng.update_weights(tiny_params, 3) == 3
        # default bumps
        assert eng.update_weights(tiny_params) == 4

    def test_fp32_push_into_a_bf16_replica(self):
        """A serve/rollout replica holds its seeded weights in the model's
        compute dtype (``serve.llm._seeded_params``); the learner pushes
        fp32 masters. The push is cast to the resident dtype — accepted,
        no retrace — and decodes exactly like an engine born on the
        cast weights."""
        import dataclasses

        from ray_tpu.serve.llm import _seeded_params

        cfg = dataclasses.replace(TINY, dtype="bfloat16")
        eng = LLMEngine(cfg, _seeded_params(gpt_init, cfg, 0, 1), ENG)
        assert {x.dtype for x in jax.tree_util.tree_leaves(eng.runner.params)} == {
            jnp.dtype("bfloat16")
        }
        sp = SamplingParams(max_tokens=8, temperature=1.0, seed=4)
        eng.generate([2, 3, 4], sp)  # every step compiled before the push
        masters = gpt_init(jax.random.PRNGKey(9), cfg)  # float32
        assert eng.update_weights(jax.device_get(masters), 1) == 1
        cast = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), masters)
        assert eng.generate([2, 3, 4], sp) == LLMEngine(cfg, cast, ENG).generate(
            [2, 3, 4], sp
        )
        assert eng.stats()["retraces"] == 0


# ---------------------------------------------------------------------------
# object-plane sync + rollout worker (cluster)
# ---------------------------------------------------------------------------


class TestWeightSync:
    def test_publish_fetch_roundtrip_chunked(self, ray_start_regular, tiny_params):
        """Tiny chunk_bytes forces many chunks; the reassembled pytree is
        bit-identical and structure-identical."""
        update = publish_weights(tiny_params, 7, chunk_bytes=16 << 10)
        assert update.version == 7
        assert len(update.chunk_refs) > 1  # actually chunked
        assert update.num_leaves == len(jax.tree_util.tree_leaves(tiny_params))
        got = fetch_params(update)
        leaves_a = jax.tree_util.tree_leaves(tiny_params)
        leaves_b = jax.tree_util.tree_leaves(got)
        assert len(leaves_a) == len(leaves_b)
        for a, b in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_apply_weight_update_engine_path(self, ray_start_regular, tiny_params):
        eng = LLMEngine(TINY, tiny_params, ENG)
        other = gpt_init(jax.random.PRNGKey(9), TINY)
        update = publish_weights(other, 2)
        assert apply_weight_update(eng, update) == 2
        assert eng.weights_version == 2

    def test_rollout_worker_trajectory_contract(self, ray_start_regular):
        """Local-mode worker: trajectories carry tokens, finite behavior
        logprobs, the submit-time version stamp, and a finish reason."""
        w = RolloutWorker(model="gpt", model_cfg=TINY, engine_config=ENG,
                          seed=0, warmup=False)
        try:
            pending = w.submit([[1, 2, 3], [3, 2, 1]], max_tokens=5,
                               temperature=1.0)
            assert pending == 2
            deadline = time.time() + 30
            trajs = []
            while len(trajs) < 2 and time.time() < deadline:
                trajs += w.poll()["trajs"]
                time.sleep(0.01)
            assert len(trajs) == 2
            for t in trajs:
                assert len(t["tokens"]) == 5
                assert len(t["logprobs"]) == 5
                assert all(np.isfinite(t["logprobs"]))
                assert t["weights_version"] == 0
                assert t["finish_reason"] == "length"
            # push through the SAME path the group uses; next submits stamp v1
            other = gpt_init(jax.random.PRNGKey(9), TINY)
            assert w.update_weights(publish_weights(other, 1)) == 1
            w.submit([[1, 2]], max_tokens=2)
            deadline = time.time() + 30
            out = []
            while not out and time.time() < deadline:
                out = w.poll()["trajs"]
                time.sleep(0.01)
            assert out and out[0]["weights_version"] == 1
        finally:
            w.stop()

    def test_distinct_seed_lanes_diverge(self, ray_start_regular):
        """Two workers with different sample_seed_base must explore
        different trajectories from the same prompt (else GRPO sees
        zero-variance batches)."""
        outs = []
        for base in (0, 1_000_003):
            w = RolloutWorker(model="gpt", model_cfg=TINY, engine_config=ENG,
                              seed=0, sample_seed_base=base, warmup=False)
            try:
                w.submit([[1, 2, 3]], max_tokens=8, temperature=1.0)
                deadline = time.time() + 30
                trajs = []
                while not trajs and time.time() < deadline:
                    trajs = w.poll()["trajs"]
                    time.sleep(0.01)
                outs.append(trajs[0]["tokens"])
            finally:
                w.stop()
        assert outs[0] != outs[1]


# ---------------------------------------------------------------------------
# serve-hosted engines accept the same push path
# ---------------------------------------------------------------------------


@pytest.fixture
def serve_instance():
    from ray_tpu import serve

    ray_tpu.init(num_cpus=8)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


# tier-1 budget (ISSUE 20): 10.9s measured — the full serve-deployment swap
# rides slow; TestWeightSwap + test_apply_weight_update_engine_path keep the
# swap mechanics in tier-1 and the rlhf-smoke CI job runs this file in full
@pytest.mark.slow
def test_serve_deployment_update_weights(serve_instance, tiny_params):
    """One sync code path (rlhf.sync.apply_weight_update) for raw actor
    engines AND serve replicas: push a published WeightUpdate through the
    deployment handle, see the version land and generation continue —
    matching a fresh engine built from the pushed params."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(
        build_llm_app(model="gpt", model_cfg=TINY, engine_config=ENG,
                      warmup=False),
        name="rlhf-push",
    )
    prompt = [1, 2, 3]
    before = handle.generate.remote(prompt, max_tokens=6).result(timeout=60)
    assert len(before) == 6
    assert handle.weights_version.remote().result(timeout=30) == 0

    other = gpt_init(jax.random.PRNGKey(9), TINY)
    update = publish_weights(other, 1)
    assert handle.update_weights.remote(update).result(timeout=60) == 1
    assert handle.weights_version.remote().result(timeout=30) == 1

    after = handle.generate.remote(prompt, max_tokens=6).result(timeout=60)
    ref_engine = LLMEngine(TINY, other, ENG)
    ref = ref_engine.generate(prompt, SamplingParams(max_tokens=6))
    assert after == ref


# ---------------------------------------------------------------------------
# the async loop end to end
# ---------------------------------------------------------------------------

TARGET = 7


def _reward(prompt, tokens):
    return sum(1 for t in tokens if t == TARGET) / max(len(tokens), 1)


# tier-1 budget (ISSUE 13): 19.7s measured on the dev box; the rlhf-smoke
# CI job runs this file's slow tier (plus the smoke module) on every push
@pytest.mark.slow
def test_async_loop_local_mode(ray_start_regular):
    """The whole loop minus actors (remote=False): poller stages, gate
    admits, learner updates, weights publish + apply, versions stamp."""
    cfg = RLHFConfig(
        model_cfg=TINY, engine_config=ENG,
        prompts=[[1, 2, 3]], reward_fn=_reward,
        num_rollout_workers=1, remote_rollouts=False, rollout_inflight=4,
        max_tokens=4, temperature=1.0, train_batch=4,
        buffer_capacity=8, lr=0.05, max_staleness=8, warmup=False,
        batch_timeout_s=60.0, seed=0,
    )
    algo = Algorithm(cfg)
    try:
        out = algo.train(3)
        real = [o for o in out if not o.get("skipped")]
        assert len(real) == 3
        assert algo.weights_version == 3
        assert algo.rollouts.versions() == [3]
        # late batches must contain post-push version stamps
        assert any(v > 0 for v in algo.stats()["last_batch_versions"])
        for o in real:
            assert o["trajectories"] == 4
            assert "learner/loss" in o
    finally:
        algo.shutdown()


@pytest.mark.slow
def test_async_rlhf_learns_with_overlap():
    """Acceptance: a tiny GPT policy trained via rlhf.Algorithm on a
    synthetic reward IMPROVES mean reward over N iterations while
    rollout and learner provably overlap (recorder events show
    rollout.finish timestamps interleaved with learner.step), weight
    pushes apply without draining, and trajectories carry correct
    weights_version stamps — the ray_tpu.rlhf.smoke run, asserted."""
    from ray_tpu.rlhf.smoke import run_smoke

    rec = run_smoke(iterations=12, num_workers=2, train_batch=16)
    assert rec["iterations"] >= 8, rec
    assert rec["overlapped"], rec
    assert rec["versions_advanced"], rec
    assert rec["improved"], rec
