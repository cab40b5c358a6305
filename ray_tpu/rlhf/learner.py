"""The learning half of the async RLHF loop: a GPT policy hosted in the
existing ``rl.learner`` machinery.

Reuse, not reinvention: ``rl.learner.Learner`` already owns the
optimizer, grad clipping, device-mesh data parallelism, and the
local-vs-remote-actor placement (``LearnerGroup``). This module only
supplies what RL-on-LLM changes — the module (a decoder-only GPT whose
``init`` is exactly the rollout engines' init, so version 0 means the
same weights everywhere) and the loss (a PPO/GRPO-style clipped
surrogate over TOKENS with off-policy importance correction).

The correction is the heart of the async design: trajectories were
sampled by engines running version ``v_behind``, the learner is at
``v_now``. Each token carries the behavior logprob captured AT SAMPLE
TIME (``models.sampling`` logprob convention), the loss recomputes the
current-policy logprob of the same token with ``token_logprobs`` under
the SAME sampling knobs, and ``ratio = exp(cur - behavior)`` is then an
exact density ratio — clipped a la PPO so a very-stale trajectory can
pull, not yank. The staleness gate (``rlhf.algorithm``) additionally
drops/down-weights whole trajectories via ``batch["weight"]``.

Batch layout (all fixed shapes — the update jits once):

* ``tokens``        (B, T) int32 — prompt + generated, right-padded
* ``prompt_len``    (B,)  int32
* ``out_tokens``    (B, O) int32 — generated ids, right-padded
* ``out_len``       (B,)  int32
* ``behavior_logp`` (B, O) float32
* ``token_mask``    (B, O) float32 — 0 where the behavior density is
  unknown (failover-resumed tokens; excluded from the loss entirely)
* ``advantage``     (B,)  float32 — group-relative (GRPO) advantage
* ``weight``        (B,)  float32 — staleness gate output (0 = masked)
* ``temperature``/``top_k``/``top_p`` (B,) — the rollout's knobs
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init
from ray_tpu.models.sampling import token_logprobs
from ray_tpu.rl.learner import LearnerGroup


class GPTPolicyModule:
    """Adapter giving ``rl.learner.Learner`` the two hooks it needs.
    ``init`` delegates to ``gpt_init`` — the same function rollout
    engines use (``serve.llm._build_model``, which runs it under one jit
    so a large model is born in its compute dtype and placement), so a
    learner and a worker seeded alike start from the same weights at
    version 0, to the compiler's last-place rounding of the init
    arithmetic; the first weight push makes them bit-identical."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def init(self, rng):
        return gpt_init(rng, self.cfg)


def rlhf_loss(clip_param: float = 0.2, kl_coeff: float = 0.0):
    """Token-level clipped surrogate with importance correction.

    ``advantage`` is per-trajectory (GRPO: reward standardized within
    the consumed batch — no value net), broadcast over that trajectory's
    tokens. ``kl_coeff > 0`` adds the standard approximate-KL penalty
    ``E[behavior_logp - cur_logp]`` pulling the policy back toward the
    behavior distribution.
    """

    def loss_fn(module: GPTPolicyModule, params, batch):
        tokens = batch["tokens"].astype(jnp.int32)
        B, T = tokens.shape
        O = batch["out_tokens"].shape[1]
        logits = gpt_forward(module.cfg, params, tokens)  # (B, T, V)
        # position prompt_len-1+j predicts generated token j
        idx = batch["prompt_len"].astype(jnp.int32)[:, None] - 1 + jnp.arange(
            O, dtype=jnp.int32
        )[None, :]
        idx = jnp.clip(idx, 0, T - 1)
        pos_logits = jnp.take_along_axis(logits, idx[:, :, None], axis=1)
        V = pos_logits.shape[-1]

        rep = lambda x: jnp.repeat(x.astype(jnp.float32), O)
        cur_lp = token_logprobs(
            pos_logits.reshape(B * O, V),
            batch["out_tokens"].reshape(B * O).astype(jnp.int32),
            rep(batch["temperature"]),
            jnp.repeat(batch["top_k"].astype(jnp.int32), O),
            rep(batch["top_p"]),
        ).reshape(B, O)

        mask = (
            jnp.arange(O, dtype=jnp.int32)[None, :]
            < batch["out_len"].astype(jnp.int32)[:, None]
        ).astype(jnp.float32)
        # token_mask zeroes positions whose behavior density is UNKNOWN
        # (failover-resumed tokens carry NaN logprobs — they must be
        # excluded, not scored as probability 1)
        mask = mask * batch["token_mask"].astype(jnp.float32)
        w = batch["weight"].astype(jnp.float32)[:, None] * mask
        denom = jnp.maximum(w.sum(), 1.0)

        log_ratio = cur_lp - batch["behavior_logp"]
        ratio = jnp.exp(log_ratio)
        adv = batch["advantage"].astype(jnp.float32)[:, None]
        surr = jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1.0 - clip_param, 1.0 + clip_param) * adv,
        )
        pi_loss = -(surr * w).sum() / denom
        # KL in clamped log space: a behavior token the CURRENT filter
        # masks out scores ~-1e30 (token_logprobs doc) — correct for the
        # ratio (exp -> 0, clipped) but it would blow the log-space KL
        # term (and a kl_coeff-weighted loss) to ~1e30 from one token
        approx_kl = -(jnp.clip(log_ratio, -20.0, 20.0) * w).sum() / denom
        clip_frac = ((jnp.abs(ratio - 1.0) > clip_param) * w).sum() / denom
        total = pi_loss + kl_coeff * approx_kl
        return total, {
            "policy_loss": pi_loss,
            "kl": approx_kl,
            "mean_ratio": (ratio * w).sum() / denom,
            "clip_frac": clip_frac,
        }

    return loss_fn


def make_learner_group(
    model_cfg: GPTConfig,
    lr: float = 1e-2,
    grad_clip: Optional[float] = 1.0,
    clip_param: float = 0.2,
    kl_coeff: float = 0.0,
    seed: int = 0,
    remote: bool = False,
) -> LearnerGroup:
    """The async loop's learner: GPT policy + rlhf loss in the shared
    ``rl.learner`` machinery (``remote=True`` places it in its own actor
    so the update stream never contends with the driver's poll loop)."""
    return LearnerGroup(
        dict(
            module_factory=lambda: GPTPolicyModule(model_cfg),
            loss_fn=rlhf_loss(clip_param, kl_coeff),
            lr=lr,
            grad_clip=grad_clip,
            seed=seed,
        ),
        remote=remote,
    )
